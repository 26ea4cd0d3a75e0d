"""The benchmark's counts of operations and bytes, from shapes alone."""

import math

import pytest

from harness import counts, spec

GB = 1e9


def _plan_bytes(name, shape):
    from repro.core.frontend.kernelgen import get_bench
    from repro.kernels.stencil import DEFAULT_BLOCKS, make_plan
    prog = get_bench(name).program
    block = DEFAULT_BLOCKS[prog.ndim]
    interior = counts.stencil_interior(shape, prog.halo[0])
    blocks = math.prod(-(-n // b) for n, b in zip(interior, block))
    return make_plan(prog, "paper").bytes_per_block(block) * blocks


@pytest.mark.parametrize("name,shape,plan_gb,min_gb,inputs,halo", [
    ("tricubic", (256, 1024, 1024), 80.3, 5.34, 4, 2),
    ("jacobi", (16384, 16384), 10.7, 2.15, 1, 1),
])
def test_stencil_bytes_per_sweep(name, shape, plan_gb, min_gb, inputs, halo):
    assert round(_plan_bytes(name, shape) / GB, 1) == plan_gb
    assert round(counts.stencil_min_bytes(shape, halo, inputs) / GB, 2) == min_gb


def test_stencil_points():
    assert counts.stencil_points((256, 1024, 1024), 2) == 252 * 1020 * 1020
    assert counts.stencil_points((16384, 16384), 1) == 16382 ** 2


def test_olmo_flops_per_token():
    cfg = spec.find_cell("olmo-1b.train").config
    s = counts.lm_sizes(cfg)
    # 16 layers of 4*2048^2 attention + 3*2048*8192 MLP, and the tied table
    assert s["params"] == 16 * (4 * 2048 ** 2 + 3 * 2048 * 8192) + 50304 * 2048
    attn = 3 * 4 * 512 * 2048 * 16
    assert counts.train_flops_per_token(cfg, 1024) == 6 * s["params"] + attn
    assert 7.25e9 < counts.train_flops_per_token(cfg, 1024) < 7.27e9


def test_olmo_param_count_matches_the_program():
    import jax
    from harness.lm import model_config
    from repro.models import build_model, unbox
    cfg = spec.find_cell("olmo-1b.train").config
    model = build_model(model_config(cfg))
    tree = jax.eval_shape(lambda: unbox(model.init(jax.random.PRNGKey(0))))
    n = sum(math.prod(x.shape) for x in jax.tree_util.tree_leaves(tree))
    assert n == counts.lm_sizes(cfg)["params"]


def test_decode_step_bound_by_bytes():
    cfg = spec.find_cell("olmo-1b.decode").config
    peaks = {"bf16_flops_s": 197e12, "hbm_bytes_s": 819e9}
    s = counts.lm_sizes(cfg)
    kv = 2 * 16 * 2000 * 16 * 16 * 128 * 2
    assert counts.decode_step_min_bytes(cfg, 16, 2000) == 2 * s["params"] + kv
    assert counts.decode_step_min_s(cfg, 16, 2000, peaks) == pytest.approx(
        (2 * s["params"] + kv) / 819e9)

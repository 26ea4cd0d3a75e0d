"""Mamba-2 against its plain reference (the chip benchmark's
``configs/mamba2-1.3b.reference.py``), at a small size on the CPU with
seeded weights: the program's loss and per-leaf gradients, the float8
control, the leaves weight decay applies to, the named scopes in the
compiled train step, and the registered configuration's published
numbers.  The program's configuration is built by the benchmark's
driver (``drivers/ssm_train_steps.py``), as the chip cell builds it."""

import importlib.util
import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.models import build_model, unbox
from repro.train import (OptConfig, adamw_update, decay_mask, init_opt_state,
                         make_train_step)

CHIP = pathlib.Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
CONFIGS = CHIP / "configs"
SMALL = {"d_model": 64, "n_layer": 2, "d_state": 16, "headdim": 16,
         "chunk_size": 16, "vocab_size": 250, "torch_dtype": "float32"}
S = 64
# float32 on both sides; the program sums the SSD chunk by chunk (16
# positions) and the reference as one (S, S) product, so the two differ
# by float32 rounding alone: under 1e-6 of the loss and of each leaf's
# gradient.  Float8 operands move them by about 1e-4 and 1e-1.
LOSS_RTOL = 1e-5
GRAD_RTOL = 1e-4


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _reference():
    return _load(CONFIGS / "mamba2-1.3b.reference.py", "mamba2_reference")


def _program_config(cfg):
    """The driver's ``model_config`` (the driver imports the benchmark's
    ``harness`` package, found beside it)."""
    if str(CHIP) not in sys.path:
        sys.path.insert(0, str(CHIP))
    driver = _load(CHIP / "drivers" / "ssm_train_steps.py", "ssm_train_steps")
    return driver.model_config(cfg)


def _file():
    return json.loads((CONFIGS / "mamba2-1.3b.json").read_text())


def _nest(flat):
    """``{"a/b": x}`` -> ``{"a": {"b": x}}``."""
    tree = {}
    for name, value in flat.items():
        *parents, leaf = name.split("/")
        node = tree
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = value
    return tree


def _flat(tree, prefix=""):
    out = {}
    for k, v in tree.items():
        if isinstance(v, dict):
            out.update(_flat(v, f"{prefix}{k}/"))
        else:
            out[f"{prefix}{k}"] = v
    return out


@pytest.fixture(scope="module")
def setup():
    ref = _reference()
    cfg = dict(_file(), **SMALL)
    model = build_model(_program_config(cfg))
    weights = ref.init_weights(jax.random.PRNGKey(3), cfg)
    params = _nest(weights)
    want = jax.eval_shape(lambda: unbox(model.init(jax.random.PRNGKey(0))))
    assert jax.tree_util.tree_structure(params) == \
        jax.tree_util.tree_structure(want)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(params)[0],
                            jax.tree_util.tree_leaves(want)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype), path
    toks = jax.random.randint(jax.random.PRNGKey(4), (1, S + 1), 0,
                              cfg["vocab_size"])
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    (loss, _), grads = jax.jit(jax.value_and_grad(model.loss, has_aux=True))(
        params, batch)
    return ref, cfg, model, weights, batch, float(loss), _flat(grads)


def _reference_grads(ref, cfg, weights, batch, quant=None):
    loss, grads = jax.jit(jax.value_and_grad(
        lambda w: ref.loss(w, batch["tokens"], batch["labels"], cfg, quant)))(
            weights)
    return float(loss), grads


def _gaps(loss, grads, ref_loss, ref_grads):
    """The loss's relative gap and, per leaf, the norm of the gradient's
    difference over the larger of the leaf's and the median leaf's
    reference norm."""
    norms = {n: float(jnp.linalg.norm(g)) for n, g in ref_grads.items()}
    median = sorted(norms.values())[len(norms) // 2]
    return abs(loss - ref_loss) / abs(ref_loss), {
        n: float(jnp.linalg.norm(grads[n] - g)) / max(norms[n], median)
        for n, g in ref_grads.items()}


def test_program_matches_reference(setup):
    ref, cfg, _, weights, batch, loss, grads = setup
    assert set(grads) == set(ref.LEAVES)
    loss_gap, grad_gaps = _gaps(loss, grads,
                                *_reference_grads(ref, cfg, weights, batch))
    assert loss_gap < LOSS_RTOL, loss_gap
    assert max(grad_gaps.values()) < GRAD_RTOL, grad_gaps


def test_float8_reference_fails_the_tolerances(setup):
    ref, cfg, _, weights, batch, loss, grads = setup
    loss_gap, grad_gaps = _gaps(loss, grads, *_reference_grads(
        ref, cfg, weights, batch, quant="fp8"))
    assert loss_gap > LOSS_RTOL or max(grad_gaps.values()) > GRAD_RTOL


def test_weight_decay_applies_to_the_published_leaves(setup):
    """The program's optimizer decays what the reference does: the
    embedding and each layer's matrices, not the stacked layers' norm
    scales, conv bias, ``A_log``, ``D`` or ``dt_bias``."""
    ref, _, model, weights, _, _, _ = setup
    mask = _flat(decay_mask(jax.eval_shape(model.init,
                                           jax.random.PRNGKey(0))))
    assert {n for n, d in mask.items() if d} == set(ref.DECAYED)
    # a step with a zero gradient moves the decayed leaves alone
    params = _nest(weights)
    opt = OptConfig(lr=1e-2, warmup_steps=0)
    zero = jax.tree_util.tree_map(jnp.zeros_like, params)
    new, _, _ = adamw_update(opt, zero, init_opt_state(params), params,
                             decay_mask(jax.eval_shape(model.init,
                                                       jax.random.PRNGKey(0))))
    moved = {n for n, a in _flat(new).items()
             if not bool(jnp.array_equal(a, weights[n]))}
    assert moved == set(ref.DECAYED)


def test_compiled_train_step_carries_the_scopes(setup):
    """The named scopes reach the compiled step's ``op_name`` metadata in
    the forward pass (and its recomputation) and in the backward pass."""
    _, _, model, weights, batch, _, _ = setup
    params = _nest(weights)
    step = jax.jit(make_train_step(model, OptConfig()))
    text = step.lower(params, init_opt_state(params), batch).compile().as_text()
    names = [line.split('op_name="', 1)[1].split('"', 1)[0]
             for line in text.splitlines() if 'op_name="' in line]
    for scope in ("mamba2_in_proj", "mamba2_conv1d", "mamba2_ssd",
                  "mamba2_gate_norm", "mamba2_out_proj"):
        assert any(scope in n and "transpose" not in n for n in names), scope
    for scope in ("mamba2_conv1d", "mamba2_ssd"):
        assert any(scope in n and "transpose" in n for n in names), scope


def test_registered_config_is_the_published_one():
    cfg = get_config("mamba2-1.3b")
    published = dict(_file(), **_file()["published"])
    assert cfg.vocab == 50288 == _program_config(published).vocab
    assert cfg.ssm_norm_eps == 1e-5 == published["norm_epsilon"]
    assert (cfg.n_layers, cfg.d_model, cfg.d_ff, cfg.n_heads) == (48, 2048, 0, 0)
    assert (cfg.ssm_state, cfg.ssm_head_dim, cfg.ssm_expand, cfg.conv_width,
            cfg.ssm_chunk) == (128, 64, 2, 4, 256)
    assert cfg.family == "ssm" and cfg.norm == "rmsnorm" and cfg.tie_embeddings

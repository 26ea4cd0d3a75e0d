"""Compile the Pallas kernels for a described TPU v5e chip (no chip
attached): the compiler refuses what interpret mode cannot see, such as
copies not aligned to the memory tiling, illegal block shapes and loads
from HBM refs.  Shapes are the ones ``chip_smoke.py`` runs on the chip.

The topology is described only inside a fixture: loading the TPU
compiler takes a process-wide lock, so no module may do it at import.
"""

import base64
import json
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.frontend.kernelgen import get_bench
from repro.kernels.conv1d import causal_conv1d
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ssd import ssd_pallas
from repro.kernels.stencil import stencil_apply

STENCIL_SHAPES = {2: (16384, 16384), 3: (256, 1024, 1024)}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a TPU executable written to the persistent cache cannot be read
    # back without a chip, so keep these compiles out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile_text(fn, one_chip, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("mode", ["naive", "paper", "tile"])
@pytest.mark.parametrize("name", ["jacobi", "tricubic"])
def test_stencil_compiles(name, mode, one_chip):
    prog = get_bench(name).program
    names = sorted(a for a in prog.arrays if a != prog.out.array)
    scalars = {s: 0.5 for s in prog.scalars}
    shape = STENCIL_SHAPES[prog.ndim]

    def fn(*xs):
        return stencil_apply(prog, dict(zip(names, xs)), scalars, mode=mode)

    text = _compile_text(fn, one_chip, *[(shape, jnp.float32)] * len(names))
    assert "tpu_custom_call" in text


def _kernel_bodies(lowered):
    """The serialized Mosaic module of each TPU custom call."""
    from jax._src.lib.mlir import ir
    bodies = []

    def walk(op):
        for region in op.regions:
            for block in region.blocks:
                for inner in block.operations:
                    if inner.operation.name == "stablehlo.custom_call":
                        config = ir.StringAttr(
                            inner.attributes["backend_config"]).value
                        bodies.append(base64.b64decode(json.loads(
                            config)["custom_call_config"]["body"]))
                    walk(inner.operation)
    walk(lowered.compiler_ir("stablehlo").operation)
    return bodies


@pytest.mark.parametrize("trace_every", [0, 251])
@pytest.mark.parametrize("name", ["jacobi", "tricubic"])
def test_stencil_trace_regions(name, trace_every, one_chip):
    """The default kernel holds no trace op; a sampling one holds the
    three named regions and compiles with custom-call region traces on."""
    from repro.kernels.stencil import REGIONS
    prog = get_bench(name).program
    names = sorted(a for a in prog.arrays if a != prog.out.array)
    scalars = {s: 0.5 for s in prog.scalars}
    shape = STENCIL_SHAPES[prog.ndim]

    def fn(*xs):
        return stencil_apply(prog, dict(zip(names, xs)), scalars,
                             mode="paper", trace_every=trace_every)

    args = [jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
            for _ in names]
    lowered = jax.jit(fn).lower(*args)
    (body,) = _kernel_bodies(lowered)
    if trace_every == 0:
        assert b"trace_start" not in body
        assert not any(r.encode() in body for r in REGIONS)
    else:
        assert b"trace_start" in body and b"trace_stop" in body
        assert all(r.encode() in body for r in REGIONS)
        text = lowered.compile(compiler_options={
            "xla_enable_custom_call_region_trace": True}).as_text()
        assert "tpu_custom_call" in text


@pytest.mark.parametrize("mode", ["naive", "shuffle"])
def test_conv1d_compiles(mode, one_chip):
    # mamba2-1.3b: conv over d_inner + 2 * d_state = 4352 channels
    B, L, C, W = 1, 2048, 4352, 4
    text = _compile_text(
        lambda x, w, b: causal_conv1d(x, w, b, mode=mode), one_chip,
        ((B, L, C), jnp.bfloat16), ((W, C), jnp.bfloat16),
        ((C,), jnp.bfloat16))
    assert "tpu_custom_call" in text


def test_flash_attention_compiles(one_chip):
    # olmo-1b: 16 heads (MHA), head dim 128
    B, S, H, Dh = 1, 2048, 16, 128
    qkv = ((B, S, H, Dh), jnp.bfloat16)
    text = _compile_text(lambda q, k, v: flash_attention(q, k, v),
                         one_chip, qkv, qkv, qkv)
    assert "tpu_custom_call" in text


def test_ssd_compiles(one_chip):
    # mamba2-1.3b: 64 heads x 64, state 128, chunk 256
    B, L, H, P, N = 1, 2048, 64, 64, 128
    text = _compile_text(
        lambda x, dt, a, b, c: ssd_pallas(x, dt, a, b, c, chunk=256),
        one_chip, ((B, L, H, P), jnp.bfloat16), ((B, L, H), jnp.float32),
        ((H,), jnp.float32), ((B, L, 1, N), jnp.bfloat16),
        ((B, L, 1, N), jnp.bfloat16))
    assert "tpu_custom_call" in text

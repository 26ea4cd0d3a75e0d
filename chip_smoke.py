"""Run the system's main paths once on a TPU and check what comes out.

    python chip_smoke.py               # one chip: stencil, kernels, serve, train
    python chip_smoke.py --four-chips  # four chips: sharded MoE train + layer

Phases (one process; nothing here starts a subprocess):

``stencil``  the paper's path: for each E5 stencil, PTXASW detection
             through a ``Compiler`` -> Pallas fetch plan, then the
             compiled kernel in the naive, paper and tile plans at
             16384^2 (2D) / 256x1024x1024 (3D), f32, against the jnp
             oracle: max|err| <= 1e-4 * max|ref|.
``kernels``  conv1d (mamba2-1.3b conv), flash attention (olmo-1b heads)
             and the SSD scan (mamba2-1.3b heads/state) compiled, in
             bf16, against their ``ref.py`` oracles.
``serve``    ``repro.launch.serve`` on olmo-1b at its published widths
             (seeded random weights); the first decode step's logits
             must match a prefill of the prompt extended by the first
             generated token.
``train``    ``repro.launch.train`` on olmo-1b at its published widths;
             the loss must be finite at every step.
``four_chips`` (only with ``--four-chips``) granite-moe-1b-a400m training
             on a 1x4 mesh, per-device memory, and one MoE layer
             sharded over four chips against the dense layer on one.

Each phase prints one line: name, PASS or FAIL, wall seconds (set-up and
compilation included; these are not benchmark numbers).  The last line is
a JSON object naming the device, printed only when every phase passed.
Without a TPU the script exits non-zero before doing any work.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.runtime import enable_compile_cache  # noqa: E402

E5 = ("jacobi", "gaussblur", "tricubic", "lapgsrb", "wave13pt")
STENCIL_SHAPES = {2: (16384, 16384), 3: (256, 1024, 1024)}
STENCIL_RTOL = 1e-4     # max|err| / max|ref|, f32
BF16_RTOL = 3e-2        # max|err| / max|ref|, bf16 kernels and MoE layer
LOGITS_RTOL = 5e-2      # decode vs prefill logits, bf16 model
SERVE_ARGS = ["--arch", "olmo-1b", "--batch", "4", "--prompt-len", "128",
              "--gen", "16"]
TRAIN_ARGS = ["--arch", "olmo-1b", "--steps", "4", "--batch", "1",
              "--seq", "1024"]
MOE_TRAIN_ARGS = ["--arch", "granite-moe-1b-a400m", "--mesh", "1x4",
                  "--steps", "3", "--batch", "4", "--seq", "1024"]


def check(cond, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def check_close(what: str, out, ref, rtol: float) -> str:
    """Require max|out - ref| <= rtol * max|ref|; return the ratio."""
    out = jnp.asarray(out, jnp.float32)
    ref = jnp.asarray(ref, jnp.float32)
    err = float(jnp.max(jnp.abs(out - ref)))
    scale = float(jnp.max(jnp.abs(ref)))
    check(np.isfinite(scale) and scale > 0, f"{what}: max|ref| = {scale}")
    check(err <= rtol * scale,
          f"{what}: max|err| {err:.3e} > {rtol:g} * max|ref| {scale:.3e}")
    return f"{what} {err / scale:.3e}"


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_stencil(shapes=STENCIL_SHAPES, seed: int = 0) -> str:
    from repro.core.driver import Compiler
    from repro.core.frontend.kernelgen import get_bench
    from repro.core.frontend.pallas_lower import synthesize_tpu
    from repro.kernels.stencil import MODES, ref as stencil_ref, stencil_apply

    cc = Compiler()
    notes = []
    for name in E5:
        bench = get_bench(name)
        prog = bench.program
        plan = synthesize_tpu(prog, max_delta=bench.max_delta, compiler=cc)
        check(plan.consistent, f"{name}: detection and fetch plan disagree")
        names = sorted(a for a in prog.arrays if a != prog.out.array)
        keys = jax.random.split(jax.random.PRNGKey(seed), len(names))
        shape = shapes[prog.ndim]
        arrays = {a: jax.random.normal(k, shape, jnp.float32)
                  for a, k in zip(names, keys)}
        scalars = {s: 0.1 * (i + 1) for i, s in enumerate(prog.scalars)}
        ref = jax.jit(functools.partial(stencil_ref.evaluate, prog,
                                        scalars=scalars))(arrays)
        for mode in MODES:
            out = jax.jit(functools.partial(
                stencil_apply, prog, scalars=scalars, mode=mode))(arrays)
            check(out.shape == ref.shape, f"{name}/{mode}: shape {out.shape}")
            notes.append(check_close(f"{name}/{mode}", out, ref, STENCIL_RTOL))
            del out
        del arrays, ref
    return "; ".join(notes)


def phase_kernels(L: int = 2048, seed: int = 0) -> str:
    from repro.kernels.conv1d import causal_conv1d, ref as conv_ref
    from repro.kernels.flash_attention import attention_ref, flash_attention
    from repro.kernels.ssd import ssd_pallas, ssd_ref

    bf16 = jnp.bfloat16
    k = iter(jax.random.split(jax.random.PRNGKey(seed), 16))
    notes = []

    # conv1d at the mamba2-1.3b conv width: d_inner + 2 * d_state
    C, W = 4096 + 2 * 128, 4
    x = jax.random.normal(next(k), (1, L, C), bf16)
    w = jax.random.normal(next(k), (W, C), bf16)
    b = jax.random.normal(next(k), (C,), bf16)
    ref = conv_ref.causal_conv1d(x, w, b)
    for mode in ("naive", "shuffle"):
        out = jax.jit(functools.partial(causal_conv1d, mode=mode))(x, w, b)
        notes.append(check_close(f"conv1d/{mode}", out, ref, BF16_RTOL))

    # flash attention at olmo-1b's heads: 16 x 128, causal
    H, Dh = 16, 128
    q, kk, v = (jax.random.normal(next(k), (1, L, H, Dh), bf16)
                for _ in range(3))
    out = jax.jit(flash_attention)(q, kk, v)
    notes.append(check_close("flash_attention", out,
                             attention_ref(q, kk, v), BF16_RTOL))

    # SSD at mamba2-1.3b: 64 heads x 64, state 128, chunk 256
    Hs, P, N, Q = 64, 64, 128, 256
    xh = jax.random.normal(next(k), (1, L, Hs, P), bf16)
    dt = jax.random.uniform(next(k), (1, L, Hs), jnp.float32, 0.001, 0.1)
    A = -jax.random.uniform(next(k), (Hs,), jnp.float32, 0.5, 2.0)
    Bm = jax.random.normal(next(k), (1, L, 1, N), bf16)
    Cm = jax.random.normal(next(k), (1, L, 1, N), bf16)
    out = jax.jit(functools.partial(ssd_pallas, chunk=Q))(xh, dt, A, Bm, Cm)
    notes.append(check_close("ssd", out, ssd_ref(xh, dt, A, Bm, Cm, chunk=Q),
                             BF16_RTOL))
    return "; ".join(notes)


def phase_serve(argv=SERVE_ARGS) -> str:
    from repro.launch import serve

    tokens = serve.main(list(argv))["tokens"]
    args = serve.parse_args(list(argv))
    cfg, model, params, batch = serve.build(args)
    check(tokens.shape == (args.batch, args.gen),
          f"tokens shape {tokens.shape}")
    check(bool(np.all((tokens >= 0) & (tokens < cfg.vocab))),
          "token id outside the vocabulary")
    # the first decode step after the prompt must agree with a prefill of
    # the prompt extended by the token it was fed (the first generated)
    first = jnp.asarray(tokens[:, 0])
    _, cache = jax.jit(functools.partial(
        model.prefill, max_len=args.prompt_len + args.gen))(params, batch)
    dec_logits, _ = jax.jit(model.decode_step)(params, first, cache)
    ext = dict(batch, tokens=jnp.concatenate(
        [batch["tokens"], first[:, None]], axis=1))
    ref_logits, _ = jax.jit(model.prefill)(params, ext)
    return (f"tokens {tokens.shape}; " +
            check_close("decode-vs-prefill logits", dec_logits, ref_logits,
                        LOGITS_RTOL))


def phase_train(argv=TRAIN_ARGS) -> str:
    from repro.launch import train

    losses = train.main(list(argv))["losses"]
    check(len(losses) > 0 and all(np.isfinite(losses)),
          f"losses {losses}")
    return "losses " + " ".join(f"{x:.4f}" for x in losses)


def phase_four_chips(argv=MOE_TRAIN_ARGS, tokens=(8, 512),
                     seed: int = 0) -> str:
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import get_config
    from repro.launch import train
    from repro.launch.mesh import make_mesh
    from repro.models.common import unbox
    from repro.models.moe import apply_moe_dense, apply_moe_sharded, init_moe

    res = train.main(list(argv))
    losses = res["losses"]
    check(len(losses) > 0 and all(np.isfinite(losses)), f"losses {losses}")
    held = [m["state_bytes"] for m in res["memory"]]
    for m in res["memory"]:
        print(f"  memory {m['device']}: params+opt state "
              f"{m['state_bytes'] / 2**30:.3f} GiB, in use "
              f"{_gib(m['bytes_in_use'])}, peak {_gib(m['peak_bytes_in_use'])}",
              flush=True)
    check(max(held) <= 0.5 * sum(held),
          f"one device holds {max(held)} of {sum(held)} state bytes")
    notes = ["losses " + " ".join(f"{x:.4f}" for x in losses)]

    # one MoE layer at published widths, no drops (capacity_factor = E/k)
    cfg = get_config("granite-moe-1b-a400m")
    E, top_k = cfg.n_experts, cfg.moe_top_k
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    params = unbox(init_moe(k1, cfg.d_model, cfg.d_ff, E, top_k,
                            jnp.bfloat16))
    x = jax.random.normal(k2, tokens + (cfg.d_model,), jnp.bfloat16)
    y_ref, _ = jax.jit(functools.partial(apply_moe_dense, top_k=top_k,
                                         n_experts=E))(params, x)
    for shape, schedule in (((1, 4), "auto"), ((4, 1), "2d")):
        mesh = make_mesh(shape, ("data", "model"))
        rep = NamedSharding(mesh, P())
        fn = jax.jit(functools.partial(
            apply_moe_sharded, top_k=top_k, n_experts=E, mesh=mesh,
            capacity_factor=float(E) / top_k, schedule=schedule))
        y, _ = fn(jax.device_put(params, rep), jax.device_put(x, rep))
        notes.append(check_close(f"moe {shape[0]}x{shape[1]} {schedule}",
                                 y, y_ref, BF16_RTOL))
    return "; ".join(notes)


def _gib(n) -> str:
    return "n/a" if n is None else f"{n / 2**30:.3f} GiB"


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def run_phases(phases) -> bool:
    ok = True
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            detail, passed = fn(), True
        except Exception:
            traceback.print_exc()
            detail, passed = "see traceback on stderr", False
        ok &= passed
        print(f"phase {name} {'PASS' if passed else 'FAIL'} "
              f"{time.perf_counter() - t0:.1f}s  {detail}", flush=True)
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip MoE phase")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 2
    if args.four_chips and len(devices) < 4:
        print(f"chip_smoke: --four-chips needs 4 devices, found "
              f"{len(devices)}", file=sys.stderr)
        return 2

    cache_dir = enable_compile_cache()
    # a compile is written to the cache (a "miss") only when it took
    # longer than jax_persistent_cache_min_compile_time_secs
    events = {"/jax/compilation_cache/cache_hits": "hits",
              "/jax/compilation_cache/cache_misses": "written",
              "/jax/compilation_cache/compile_requests_use_cache": "requests"}
    cache = dict.fromkeys(events.values(), 0)

    def count(event, **_):
        if event in events:
            cache[events[event]] += 1

    jax.monitoring.register_event_listener(count)

    if args.four_chips:
        phases = [("four_chips", phase_four_chips)]
    else:
        phases = [("stencil", phase_stencil), ("kernels", phase_kernels),
                  ("serve", phase_serve), ("train", phase_train)]
    ok = run_phases(phases)
    print(f"compile cache {cache_dir}: {cache['hits']} hits, "
          f"{cache['written']} written, of {cache['requests']} compiles",
          flush=True)
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

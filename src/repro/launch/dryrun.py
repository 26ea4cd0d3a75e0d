import os
# 512 virtual host devices stand in for the production mesh; pin the CPU
# platform so the dry-run never takes an attached accelerator
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this lowers the real step function (train_step /
prefill_step / decode_step) against ShapeDtypeStruct stand-ins (no
allocation), compiles it for the production mesh, and records:

  * ``compiled.memory_analysis()``  — per-device bytes (proves it fits)
  * ``compiled.cost_analysis()``    — HLO FLOPs / bytes for the roofline
  * collective bytes by opcode, parsed from the post-SPMD HLO text
    (all-gather / all-reduce / reduce-scatter / all-to-all /
    collective-permute)

Results are written incrementally to experiments/dryrun/ as JSON; the
roofline analysis (benchmarks/roofline.py, EXPERIMENTS.md §Roofline)
reads from there.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun                 # all cells
  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-9b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --multi-pod     # 2x16x16
"""

import argparse
import json
import pathlib
import re
import time
import traceback
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHS, SHAPES, cell_applicable, get_config
from repro.configs.base import ModelConfig, ShapeSpec
from repro.launch.mesh import make_production_mesh
from repro.models import build_model, unbox
from repro.models.common import LogicalArray
from repro.sharding import param_shardings, shard_batch_spec
from repro.train import OptConfig, OptState, make_train_step

OUT_DIR = pathlib.Path(__file__).resolve().parents[3] / "experiments" / "dryrun"

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(bf16|f16|f32|f64|s8|u8|s16|u16|s32|u32|s64|u64|pred)"
                       r"\[([0-9,]*)\]")


def _shape_bytes(m: re.Match) -> int:
    dt, dims = m.group(1), m.group(2)
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dt]


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum operand bytes of every collective op in post-SPMD HLO."""
    out = {c: 0 for c in _COLLECTIVES}
    out["count"] = 0
    for line in hlo_text.splitlines():
        ls = line.strip()
        m = re.match(r"%?[\w.\-]+\s*=\s*[^=]*?\b"
                     r"(all-gather|all-reduce|reduce-scatter|all-to-all|"
                     r"collective-permute)(?:-start|-done)?\(", ls)
        if not m:
            continue
        op = m.group(1)
        if "-done(" in ls:      # avoid double counting start/done pairs
            continue
        # operand shapes appear inside the parens
        paren = ls[ls.index("("):]
        nbytes = sum(_shape_bytes(sm) for sm in _SHAPE_RE.finditer(paren))
        out[op] += nbytes
        out["count"] += 1
    return out


# ---------------------------------------------------------------------------
# abstract inputs
# ---------------------------------------------------------------------------

def _sds(shape, dtype, mesh, spec) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(mesh, spec))


def input_specs(cfg: ModelConfig, shape: ShapeSpec, mesh) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for the step-function batch."""
    B, S = shape.global_batch, shape.seq_len
    bspec = shard_batch_spec(mesh, (B, S))
    batch: Dict[str, Any] = {}
    if shape.kind in ("train", "prefill"):
        batch["tokens"] = _sds((B, S), jnp.int32, mesh, bspec)
        if shape.kind == "train":
            batch["labels"] = _sds((B, S), jnp.int32, mesh, bspec)
    if cfg.family == "vlm":
        batch["media"] = _sds((B, cfg.n_media_tokens, cfg.d_model),
                              jnp.bfloat16, mesh, shard_batch_spec(
                                  mesh, (B, cfg.n_media_tokens, cfg.d_model)))
    if cfg.family == "audio":
        batch["frames"] = _sds((B, cfg.n_frames, cfg.d_model),
                               jnp.bfloat16, mesh, shard_batch_spec(
                                   mesh, (B, cfg.n_frames, cfg.d_model)))
    return batch


def _batch_axes(mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def cache_specs(model, cfg: ModelConfig, shape: ShapeSpec, mesh):
    """Abstract KV/state cache with production shardings."""
    B, S = shape.global_batch, shape.seq_len
    abstract = jax.eval_shape(lambda: model.init_cache(B, S))
    baxes = _batch_axes(mesh)
    b_spec = baxes if len(baxes) > 1 else (baxes[0] if baxes else None)
    bsize = 1
    for a in baxes:
        bsize *= mesh.shape[a]
    tp = mesh.shape.get("model", 1)

    def annotate(path: str, x: jax.ShapeDtypeStruct):
        nd = len(x.shape)
        parts = [None] * nd
        if path == "pos":
            parts[0] = b_spec if B % max(bsize, 1) == 0 else None
        elif path in ("media", "memory"):
            if x.shape[0] % bsize == 0:
                parts[0] = b_spec
        elif path in ("k", "v", "attn_k", "attn_v"):
            # (..., B, S, KV, Dh)
            if x.shape[nd - 4] % bsize == 0:
                parts[nd - 4] = b_spec
            if x.shape[nd - 2] % tp == 0:
                parts[nd - 2] = "model"
        elif path == "conv":
            # (L, B, W-1, C)
            if x.shape[1] % bsize == 0:
                parts[1] = b_spec
            if x.shape[3] % tp == 0:
                parts[3] = "model"
        elif path == "ssm":
            # (L, B, H, N, P)
            if x.shape[1] % bsize == 0:
                parts[1] = b_spec
            if x.shape[2] % tp == 0:
                parts[2] = "model"
        return _sds(x.shape, x.dtype, mesh, P(*parts))

    return {k: annotate(k, v) for k, v in abstract.items()}


def param_struct(model, mesh):
    """(ShapeDtypeStruct params tree with shardings, boxed tree)."""
    from repro.sharding.rules import rules_for
    boxed = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    shardings = param_shardings(boxed, mesh, rules=rules_for(model.cfg, mesh))

    def leaf(b: LogicalArray, s):
        return jax.ShapeDtypeStruct(b.value.shape, b.value.dtype, sharding=s)

    sds = jax.tree_util.tree_map(
        leaf, boxed, shardings,
        is_leaf=lambda x: isinstance(x, LogicalArray))
    return sds, boxed


def opt_struct(params_sds):
    mu = jax.tree_util.tree_map(
        lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32,
                                       sharding=p.sharding), params_sds)
    nu = jax.tree_util.tree_map(
        lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32,
                                       sharding=p.sharding), params_sds)
    count = jax.ShapeDtypeStruct((), jnp.int32)
    return OptState(mu=mu, nu=nu, count=count)


# ---------------------------------------------------------------------------
# cell runner
# ---------------------------------------------------------------------------

def lower_cell(arch: str, shape_name: str, multi_pod: bool = False,
               cfg_override: Optional[ModelConfig] = None) -> Dict[str, Any]:
    cfg = cfg_override or get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = cell_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "skipped": why}

    mesh = make_production_mesh(multi_pod=multi_pod)
    model = build_model(cfg, mesh)
    t0 = time.time()
    params_sds, _boxed = param_struct(model, mesh)

    if shape.kind == "train":
        step = make_train_step(model, OptConfig())
        opt_sds = opt_struct(params_sds)
        batch = input_specs(cfg, shape, mesh)
        fn = jax.jit(step, donate_argnums=(0, 1))
        lowered = fn.lower(params_sds, opt_sds, batch)
    elif shape.kind == "prefill":
        batch = input_specs(cfg, shape, mesh)
        fn = jax.jit(lambda p, b: model.prefill(p, b))
        lowered = fn.lower(params_sds, batch)
    else:  # decode: one new token against a seq_len cache
        cache = cache_specs(model, cfg, shape, mesh)
        B = shape.global_batch
        baxes = _batch_axes(mesh)
        bsz = 1
        for a in baxes:
            bsz *= mesh.shape[a]
        tok_spec = (P(baxes if len(baxes) > 1 else baxes[0])
                    if B % bsz == 0 else P())
        tokens = _sds((B,), jnp.int32, mesh, tok_spec)
        fn = jax.jit(lambda p, t, c: model.decode_step(p, t, c),
                     donate_argnums=(2,))
        lowered = fn.lower(params_sds, tokens, cache)

    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    from repro.launch.hlo_analysis import analyze
    stats = analyze(hlo)   # trip-count-aware (scan bodies x trip count)

    result = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": 512 if multi_pod else 256,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_bytes": getattr(
                mem, "generated_code_size_in_bytes", None),
        },
        # per-device, trip-count-corrected (launch/hlo_analysis.py)
        "analyzed": {
            "matmul_flops": stats.flops,
            "bytes_hbm": stats.bytes_hbm,
            "bytes_accessed": stats.bytes_accessed,
            "collective_bytes": stats.collective_bytes,
            "collective_count": stats.collective_count,
            "n_while": stats.n_while,
            "trip_counts": sorted(stats.trip_counts, reverse=True)[:16],
        },
        # raw XLA numbers (while bodies single-counted; reference only)
        "cost_raw": {
            "flops": cost.get("flops"),
            "bytes_accessed": cost.get("bytes accessed"),
            "transcendentals": cost.get("transcendentals"),
        },
    }
    return result


def run(archs, shapes, multi_pod: bool, force: bool = False,
        out_dir: Optional[pathlib.Path] = None) -> None:
    out_dir = out_dir or OUT_DIR
    mesh_tag = "2x16x16" if multi_pod else "16x16"
    (out_dir / mesh_tag).mkdir(parents=True, exist_ok=True)
    for arch in archs:
        for shape_name in shapes:
            path = out_dir / mesh_tag / f"{arch}__{shape_name}.json"
            if path.exists() and not force:
                print(f"[skip] {arch} x {shape_name} ({mesh_tag}) cached")
                continue
            print(f"[cell] {arch} x {shape_name} ({mesh_tag}) ...",
                  flush=True)
            try:
                res = lower_cell(arch, shape_name, multi_pod)
            except Exception as e:  # noqa: BLE001 — record the failure
                res = {"arch": arch, "shape": shape_name, "mesh": mesh_tag,
                       "error": f"{type(e).__name__}: {e}",
                       "traceback": traceback.format_exc()[-2000:]}
                print(f"  FAILED: {type(e).__name__}: {e}", flush=True)
            path.write_text(json.dumps(res, indent=2))
            if "error" not in res and "skipped" not in res:
                print(f"  ok: compile {res['compile_s']}s "
                      f"flops/dev={res['analyzed']['matmul_flops']:.3e} "
                      f"coll={res['analyzed']['collective_count']}",
                      flush=True)
            elif "skipped" in res:
                print(f"  skipped: {res['skipped']}", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="single arch id")
    ap.add_argument("--shape", default=None, help="single shape id")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()
    archs = [args.arch] if args.arch else ARCHS
    shapes = [args.shape] if args.shape else list(SHAPES)
    if args.both_meshes:
        run(archs, shapes, multi_pod=False, force=args.force)
        run(archs, shapes, multi_pod=True, force=args.force)
    else:
        run(archs, shapes, multi_pod=args.multi_pod, force=args.force)


if __name__ == "__main__":
    main()

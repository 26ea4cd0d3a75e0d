"""End-to-end training driver.

Laptop-scale by default (reduced config, 1-device mesh) but the exact
code path a fleet launcher would run: deterministic resumable data,
jit'd train step with explicit shardings, async atomic checkpoints,
restart-from-latest, heartbeat + straggler hooks.

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch olmo-1b \
      --steps 200 --reduced --batch 8 --seq 128
  PYTHONPATH=src python -m repro.launch.train --arch mamba2-1.3b \
      --reduced --steps 50 --ckpt-dir /tmp/ck --resume
"""

from __future__ import annotations

import argparse
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint import CheckpointStore
from repro.configs import get_config, reduced
from repro.data import DataConfig, TokenPipeline
from repro.launch.mesh import make_mesh
from repro.models import build_model, unbox
from repro.runtime import Heartbeat, StragglerDetector, enable_compile_cache
from repro.sharding import batch_sharding, param_shardings
from repro.sharding.rules import rules_for
from repro.train import OptConfig, OptState, init_opt_state, make_train_step


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--mesh", default="1x1",
                    help="DxM data x model mesh (requires that many devices)")
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    d, m = (int(x) for x in args.mesh.split("x"))
    mesh = make_mesh((d, m), ("data", "model"))
    model = build_model(cfg, mesh if d * m > 1 else None)

    boxed = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    shardings = param_shardings(boxed, mesh, rules=rules_for(cfg, mesh))
    params = jax.jit(lambda k: unbox(model.init(k)),
                     out_shardings=shardings)(jax.random.PRNGKey(0))
    opt_cfg = OptConfig(lr=args.lr, warmup_steps=max(args.steps // 20, 5),
                        total_steps=args.steps)
    # built on the devices, each moment sharded like its parameter
    opt_state = jax.jit(init_opt_state, out_shardings=OptState(
        mu=shardings, nu=shardings,
        count=NamedSharding(mesh, P())))(params)

    pipe = TokenPipeline(DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                                    global_batch=args.batch))
    step_fn = jax.jit(make_train_step(model, opt_cfg, accum_steps=args.accum),
                      donate_argnums=(0, 1))

    start_step = 0
    store: Optional[CheckpointStore] = None
    if args.ckpt_dir:
        store = CheckpointStore(args.ckpt_dir)
        if args.resume:
            hit = store.restore_latest((params, opt_state))
            if hit is not None:
                start_step, (params, opt_state), extra = hit
                print(f"[resume] from step {start_step}")

    hb = Heartbeat(["host0"])
    straggler = StragglerDetector()
    bshard = batch_sharding(mesh)
    losses = []
    t_start = time.time()
    for step in range(start_step, args.steps):
        batch_np = pipe.batch_at(step)
        batch = {k: jax.device_put(v, bshard) for k, v in batch_np.items()}
        if cfg.family == "vlm":
            batch["media"] = jnp.zeros(
                (args.batch, cfg.n_media_tokens, cfg.d_model), jnp.float32)
        if cfg.family == "audio":
            batch["frames"] = jnp.zeros(
                (args.batch, cfg.n_frames, cfg.d_model), jnp.float32)
        t0 = time.time()
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        loss = float(metrics["loss"])
        losses.append(loss)
        hb.beat("host0", step)
        straggler.observe_step({"host0": time.time() - t0})
        if step % args.log_every == 0 or step == args.steps - 1:
            print(f"step {step:5d}  loss {loss:.4f}  "
                  f"lr {float(metrics['lr']):.2e}  "
                  f"gnorm {float(metrics['grad_norm']):.3f}  "
                  f"{time.time() - t0:.2f}s")
        if store and (step + 1) % args.ckpt_every == 0:
            store.save_async(step + 1, (params, opt_state),
                             extra={"data_step": step + 1})
    if store:
        store.wait()
        store.save(args.steps, (params, opt_state),
                   extra={"data_step": args.steps})
    wall = time.time() - t_start
    print(f"[done] {args.steps - start_step} steps in {wall:.1f}s; "
          f"loss {losses[0]:.4f} -> {losses[-1]:.4f}")
    return {"first_loss": losses[0], "last_loss": losses[-1],
            "losses": losses, "steps": args.steps, "wall_s": wall,
            "memory": device_memory(mesh, (params, opt_state))}


def device_memory(mesh, state) -> list:
    """Per mesh device: the bytes of ``state`` it holds, and the
    backend's own counters where it reports them."""
    held = {d: 0 for d in mesh.devices.flat}
    for leaf in jax.tree_util.tree_leaves(state):
        for shard in leaf.addressable_shards:
            held[shard.device] += shard.data.nbytes
    out = []
    for d, n in held.items():
        stats = d.memory_stats() or {}
        out.append({"device": str(d), "state_bytes": n,
                    "bytes_in_use": stats.get("bytes_in_use"),
                    "peak_bytes_in_use": stats.get("peak_bytes_in_use")})
    return out


if __name__ == "__main__":
    main()

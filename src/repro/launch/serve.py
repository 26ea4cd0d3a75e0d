"""Serving driver: batched prefill + decode with a simple request queue.

Laptop-scale demo of the serve path every decode dry-run cell lowers:
continuous batched greedy decoding against a reduced-config model.

  PYTHONPATH=src python -m repro.launch.serve --arch yi-9b --reduced \
      --batch 4 --prompt-len 32 --gen 16
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, reduced
from repro.data import DataConfig, TokenPipeline
from repro.models import build_model, unbox
from repro.runtime import enable_compile_cache
from repro.serve import generate


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-9b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    return ap.parse_args(argv)


def build(args: argparse.Namespace):
    """The served model, its seeded parameters and the prompt batch:
    ``(cfg, model, params, batch)``, the same for the same ``args``."""
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    model = build_model(cfg)
    params = jax.jit(lambda k: unbox(model.init(k)))(jax.random.PRNGKey(0))

    pipe = TokenPipeline(DataConfig(vocab=cfg.vocab, seq_len=args.prompt_len,
                                    global_batch=args.batch))
    batch = {k: jnp.asarray(v) for k, v in pipe.batch_at(0).items()}
    del batch["labels"]
    if cfg.family == "vlm":
        batch["media"] = jnp.zeros(
            (args.batch, cfg.n_media_tokens, cfg.d_model), jnp.float32)
    if cfg.family == "audio":
        batch["frames"] = jnp.zeros(
            (args.batch, cfg.n_frames, cfg.d_model), jnp.float32)
    return cfg, model, params, batch


def main(argv=None) -> dict:
    args = parse_args(argv)
    enable_compile_cache()
    cfg, model, params, batch = build(args)

    t0 = time.time()
    out = generate(model, params, batch, n_tokens=args.gen,
                   temperature=args.temperature,
                   max_len=args.prompt_len + args.gen)
    out = np.asarray(out)
    wall = time.time() - t0
    tps = args.batch * args.gen / wall
    print(f"[serve] {args.batch} requests x {args.gen} tokens "
          f"in {wall:.2f}s ({tps:.1f} tok/s)")
    print("sample continuation:", out[0][:12].tolist())
    return {"tokens": out, "wall_s": wall, "tok_per_s": tps}


if __name__ == "__main__":
    main()

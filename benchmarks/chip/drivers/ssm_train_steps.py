"""Driver for training traffic on a Mamba-2 language model: the program's
jitted, donated train step, as ``drivers/train_steps.py`` drives it.

The unit, the queue of ``ahead_steps`` steps, the drain, ``checks``,
``control`` and ``compare`` are that driver's.  What differs is named
here alone:

- the program's ``ModelConfig``, built from the file's own keys (layers,
  d_model, vocabulary and its padding, d_state, headdim, expand, d_conv,
  chunk, norm eps and dtype);
- leaves named by their whole tree path (``blocks/ln/scale`` and
  ``ln_f/scale`` both end in ``scale``);
- the counts (``harness.ssm_counts``), and for the per-layer readers of
  a traced run, the compiled step's HLO text (``hlo_text``).
"""

from __future__ import annotations

import functools
from collections import deque
from typing import Dict

import jax
import jax.numpy as jnp

from harness import ssm_counts
from harness.context import seed_key
from harness.spec import HERE, load_module

_train = load_module(HERE / "drivers" / "train_steps.py")
CHECKED_STEPS = _train.CHECKED_STEPS


def model_config(cfg: Dict):
    """The program's ModelConfig for this file's sizes."""
    from repro.configs import get_config
    if cfg["ngroups"] != 1:
        raise ValueError(f"ngroups {cfg['ngroups']}: the program has one group")
    pad = cfg["pad_vocab_size_multiple"]
    return get_config(cfg["program_arch"]).replace(
        n_layers=cfg["n_layer"], d_model=cfg["d_model"],
        vocab=-(-cfg["vocab_size"] // pad) * pad, pad_vocab_multiple=pad,
        ssm_state=cfg["d_state"], ssm_head_dim=cfg["headdim"],
        ssm_expand=cfg["expand"], conv_width=cfg["d_conv"],
        ssm_chunk=cfg["chunk_size"], ssm_norm_eps=cfg["norm_epsilon"],
        dtype=cfg["torch_dtype"])


def leaf_name(path) -> str:
    return "/".join(str(k.key) for k in path)


def program_params(model, weights: Dict):
    """The program's parameter tree holding the reference's weight
    arrays (no copy), checked against the program's own ``init``."""
    from repro.models import unbox
    abstract = jax.eval_shape(lambda: unbox(model.init(jax.random.PRNGKey(0))))
    paths, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    if {leaf_name(p) for p, _ in paths} != set(weights):
        raise ValueError(f"the program's leaves {[leaf_name(p) for p, _ in paths]}"
                         f" are not the reference's {sorted(weights)}")
    leaves = []
    for path, want in paths:
        got = weights[leaf_name(path)]
        if got.shape != want.shape or got.dtype != want.dtype:
            raise ValueError(f"{leaf_name(path)}: the program wants "
                             f"{want.shape} {want.dtype}, the weights are "
                             f"{got.shape} {got.dtype}")
        leaves.append(got)
    return jax.tree_util.tree_unflatten(treedef, leaves)


def leaf_norms(tree) -> Dict[str, float]:
    """Per-leaf float32 L2 norms of a tree shaped like the parameters."""
    paths = jax.tree_util.tree_flatten_with_path(tree)[0]
    norms = jax.jit(lambda xs: [jnp.linalg.norm(
        x.astype(jnp.float32).ravel()) for x in xs])([x for _, x in paths])
    return {leaf_name(p): float(n) for (p, _), n in zip(paths, norms)}


class Driver(_train.Driver):
    def __init__(self, ctx):
        from repro.data import DataConfig, TokenPipeline
        from repro.models import build_model
        from repro.train import OptConfig, init_opt_state, make_train_step

        self.ctx = ctx
        cfg, tr = ctx.config, ctx.traffic
        self.ref = ctx.reference()
        self.key = seed_key(ctx.seed)
        model = build_model(model_config(cfg))
        with ctx.part("weights"):
            weights = jax.jit(functools.partial(self.ref.init_weights, cfg=cfg))(
                self.key)
            self.params = program_params(model, weights)
            del weights
            self.opt_state = jax.jit(init_opt_state)(self.params)
        self.opt = dict(tr["optimizer"])
        self.step_fn = jax.jit(make_train_step(model, OptConfig(**self.opt)),
                               donate_argnums=(0, 1))
        self.pipe = TokenPipeline(DataConfig(
            vocab=cfg["vocab_size"], seq_len=tr["seq_len"],
            global_batch=tr["batch"], seed=ctx.seed))
        self.tokens_per_step = tr["batch"] * tr["seq_len"]
        self.ahead = tr["ahead_steps"]
        self.step = 0
        self.losses = []
        self.pending = deque()
        self.steps_in_window = 0
        # the step's arguments as shapes, for ``hlo_text``
        self.step_args = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding),
            (self.params, self.opt_state,
             {k: jax.device_put(v) for k, v in self.pipe.batch_at(0).items()}))
        with ctx.part("first_step"):
            self.unit()
        with ctx.part("first_grad"):
            self.grad_norms = {n: v / (1 - self.opt["b1"]) for n, v in
                               leaf_norms(self.opt_state.mu).items()}
            self.first_grad = self._first_grad()
        with ctx.part("checked_steps"):
            for _ in range(CHECKED_STEPS - 1):
                self.unit()
        with ctx.part("change_norms"):
            self.change_norms = self._change_norms()
        self.finish()
        self.first_losses = list(self.losses)
        self.steps_in_window = 0

    def _first_grad(self):
        """The first step's gradient as the optimizer took it (its first
        moment over 1 - b1), per leaf in float32 on the host's CPU
        device."""
        cpu = jax.devices("cpu")[0]
        unbias = jax.jit(lambda m: m / (1 - self.opt["b1"]))
        out = {}
        for path, m in jax.tree_util.tree_flatten_with_path(
                self.opt_state.mu)[0]:
            out[leaf_name(path)] = unbias(jax.device_put(m, cpu))
        return jax.block_until_ready(out)

    def _change_norms(self):
        """Per leaf, the norm of the parameters' change since the seeded
        weights, made anew leaf by leaf so that they never sit beside the
        whole state."""
        cfg = self.ctx.config
        out = {}
        for path, p in jax.tree_util.tree_flatten_with_path(self.params)[0]:
            name = leaf_name(path)
            fn = jax.jit(lambda p, k, name=name: jnp.linalg.norm(
                (p.astype(jnp.float32) - self.ref.init_leaf(
                    k, name, cfg).astype(jnp.float32)).ravel()))
            out[name] = float(fn(p, self.key))
        return out

    def _hlo_text(self) -> str:
        return self.step_fn.lower(*self.step_args).compile().as_text()

    def facts(self, window_s: float):
        cfg, tr = self.ctx.config, self.ctx.traffic
        tokens = self.steps_in_window * self.tokens_per_step
        return {
            "attempted": self.steps_in_window,
            "checked": CHECKED_STEPS,
            "units": self.steps_in_window,
            "end_to_end": {"train_tokens_s": tokens / window_s},
            "tokens": tokens,
            "flops_per_token": ssm_counts.train_flops_per_token(
                cfg, tr["seq_len"]),
            "ssd_train_step": ssm_counts.ssd_train_step(
                cfg, tr["seq_len"], tr["batch"]),
            "hlo_text": self._hlo_text,
            "window_s": window_s,
        }

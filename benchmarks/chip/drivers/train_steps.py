"""Driver for training traffic: the program's jitted, donated train step.

Built as ``repro.launch.train`` builds it: ``make_train_step`` under
``jax.jit`` with parameters and optimizer state donated, the optimizer
state made by the program's ``init_opt_state`` under ``jit``.  Batches
come from the program's ``TokenPipeline`` seeded with ``--seed``, are
placed on the device per step, and the host reads the loss after every
step, as the launch loop does.  The weights are made from the seed by
the configuration's reference (``init_weights``) in one jitted call.

Set-up drives the step through its first three steps with the window's
own call and feed; their losses, the first step's gradient (from the
optimizer's first moment) and the change of the parameters after the
three are kept and compared with the reference after the window.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from harness import counts
from harness.context import seed_key
from harness.lm import (leaf_name, leaf_norms, model_config, program_params,
                        worst_leaf_gap)
from harness.trace import span

CHECKED_STEPS = 3
# a leaf whose reference gradient is under this share of the median
# leaf's moves under Adam by round-off alone; its change is not compared
NOUGHT_GRAD = 1e-3


class Driver:
    def __init__(self, ctx):
        from repro.data import DataConfig, TokenPipeline
        from repro.models import build_model
        from repro.train import OptConfig, init_opt_state, make_train_step

        self.ctx = ctx
        cfg, tr = ctx.config, ctx.traffic
        self.ref = ctx.reference()
        self.key = seed_key(ctx.seed)
        model = build_model(model_config(cfg))
        with span("weights"):
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
        self.step = 0
        self.losses = []
        self.steps_in_window = 0
        self.unit()
        self.grad_norms = {n: v / (1 - self.opt["b1"])
                           for n, v in leaf_norms(self.opt_state.mu).items()}
        for _ in range(CHECKED_STEPS - 1):
            self.unit()
        self.change_norms = self._change_norms()
        self.first_losses = list(self.losses)
        self.steps_in_window = 0

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

    def begin(self):
        self.steps_in_window = 0

    def unit(self):
        with span("train_step"):
            batch = {k: jax.device_put(v)
                     for k, v in self.pipe.batch_at(self.step).items()}
            self.params, self.opt_state, metrics = self.step_fn(
                self.params, self.opt_state, batch)
            self.losses.append(float(metrics["loss"]))
        self.step += 1
        self.steps_in_window += 1

    def facts(self, window_s: float):
        tr = self.ctx.traffic
        tokens = self.steps_in_window * self.tokens_per_step
        return {
            "attempted": self.steps_in_window,
            "checked": CHECKED_STEPS,
            "units": self.steps_in_window,
            "end_to_end": {"train_tokens_s": tokens / window_s},
            "tokens": tokens,
            "flops_per_token": counts.train_flops_per_token(
                self.ctx.config, tr["seq_len"]),
            "window_s": window_s,
        }

    def release(self):
        self.params = self.opt_state = self.step_fn = None

    def _batches(self):
        return [(b["tokens"], b["labels"]) for b in
                (self.pipe.batch_at(i) for i in range(CHECKED_STEPS))]

    def _reference(self, **kw):
        with span("reference"):
            return self.ref.train_steps(self.key, self.ctx.config, self.opt,
                                        self._batches(), **kw)

    def checks(self):
        mine = {"losses": self.first_losses, "grad_norms": self.grad_norms,
                "change_norms": self.change_norms}
        gaps = compare(mine, self._reference())
        limits = self.ctx.traffic["limits"]
        return {k: {"value": v, "limit": limits[k]} for k, v in gaps.items()}

    def control(self):
        """The reference in fp8 (the control) and the reference over half
        of each batch's tokens (a fault), each in the program's place,
        with every side's losses."""
        ref = self._reference()
        fp8 = self._reference(quant="fp8")
        half = self._reference(half_batch=True)
        return {"control": compare(fp8, ref),
                "half_batch": compare(half, ref),
                "losses": {"program": self.first_losses,
                           "reference": ref["losses"],
                           "control": fp8["losses"],
                           "half_batch": half["losses"]}}


def compare(mine, ref):
    """The three numbers compared: the relative gap of the first step's
    loss, and, by the worst leaf, the gap of the first gradient's norm and
    of the change's norm after the checked steps (leaves whose reference
    gradient is nought left out of the latter).

    The later steps' losses are not compared: at the warm-up's learning
    rates Adam's first updates move every weight by about the learning
    rate, the loss moves by nats from step to step, and sound runs' gaps
    there swing from 6e-4 to 1.3e-2 with the seed, against about 1e-4 at
    the first step on one TPU v5e (PERF.md, section 6)."""
    g = ref["grad_norms"]
    leaves = sorted(g)
    median = sorted(g.values())[len(g) // 2]
    moved = [n for n in leaves if g[n] >= NOUGHT_GRAD * median]
    return {
        "first_loss_rel_gap": abs(mine["losses"][0] - ref["losses"][0])
        / abs(ref["losses"][0]),
        "grad_norm_gap": worst_leaf_gap(mine["grad_norms"], g, leaves),
        "change_norm_gap": worst_leaf_gap(mine["change_norms"],
                                          ref["change_norms"], moved),
    }

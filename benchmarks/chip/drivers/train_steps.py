"""Driver for training traffic: the program's jitted, donated train step.

Built as ``repro.launch.train`` builds it: ``make_train_step`` under
``jax.jit`` with parameters and optimizer state donated, the optimizer
state made by the program's ``init_opt_state`` under ``jit``.  Batches
come from the program's ``TokenPipeline`` seeded with ``--seed``, are
placed on the device per step.  The host reads each step's loss
``ahead_steps`` steps late (the traffic's), so that that many steps
stay queued on the device while the host waits in a read; ``finish``
waits for every step sent.  The weights are made from the seed by
the configuration's reference (``init_weights``) in one jitted call.

Set-up drives the step through its first three steps with the window's
own call and feed; their losses, the first step's gradient (from the
optimizer's first moment, copied to the host) and the change of the
parameters after the three are kept and compared with the reference
after the window.
"""

from __future__ import annotations

import functools
import sys
from collections import deque

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

    def begin(self):
        self.steps_in_window = 0

    def unit(self):
        with span("train_step"):
            batch = {k: jax.device_put(v)
                     for k, v in self.pipe.batch_at(self.step).items()}
            self.params, self.opt_state, metrics = self.step_fn(
                self.params, self.opt_state, batch)
            self.pending.append(metrics["loss"])
            if len(self.pending) > self.ahead:
                self.losses.append(float(self.pending.popleft()))
        self.step += 1
        self.steps_in_window += 1

    def finish(self):
        """Waits for every step sent, reading their losses in order."""
        with span("train_drain"):
            while self.pending:
                self.losses.append(float(self.pending.popleft()))

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
            out = self.ref.train_steps(self.key, self.ctx.config, self.opt,
                                       self._batches(), **kw)
        print("[reference] " + ", ".join(
            f"{k} {v:.3f} s" for k, v in out.pop("seconds").items()),
            file=sys.stderr)
        return out

    def _mine(self):
        return {"losses": self.first_losses, "grad_norms": self.grad_norms,
                "change_norms": self.change_norms}

    def checks(self):
        ref = self._reference(grad_against=self.first_grad)
        self.first_grad = None
        gaps = compare(self._mine(), ref, ref["grad_diff_norms"])
        limits = self.ctx.traffic["limits"]
        return {k: {"value": v, "limit": limits[k]} for k, v in gaps.items()
                if k in limits}

    def control(self):
        """The program's numbers (as ``checks`` gives them), and the same
        numbers of the reference in fp8 (the control) and of the
        reference over half of each batch's tokens (a fault), each in the
        program's place, with every side's losses."""
        ref = self._reference(keep_grad=True)
        against = ref.pop("first_grad")
        diff = jax.jit(lambda a, b: jnp.linalg.norm((a - b).ravel()))
        program = compare(self._mine(), ref, {
            n: float(diff(a, against[n])) for n, a in self.first_grad.items()})
        self.first_grad = None
        fp8 = self._reference(quant="fp8", grad_against=against)
        half = self._reference(half_batch=True, grad_against=against)
        return {"program": program,
                "control": compare(fp8, ref, fp8["grad_diff_norms"]),
                "half_batch": compare(half, ref, half["grad_diff_norms"]),
                "losses": {"program": self.first_losses,
                           "reference": ref["losses"],
                           "control": fp8["losses"],
                           "half_batch": half["losses"]}}


def compare(mine, ref, grad_diff):
    """Four readings: the relative gap of the first step's loss; by the
    worst leaf, the gap of the first gradient's norm and of the change's
    norm after the checked steps (leaves whose reference gradient is
    nought left out of the latter); and by the worst leaf the norm of the
    first gradient's difference from the reference's (``grad_diff``, per
    leaf), each leaf against the larger of its own and the median leaf's
    reference norm.  The last follows the gradient's direction, which the
    gaps of norms and a mean loss do not: float8's rounding leaves those
    about where bfloat16's does on some seeds.  A reading is compared
    where the traffic gives it a limit; the first step's loss gap has
    none, since neither the control nor a fault reads far enough above
    sound runs (PERF.md, section 6).

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
        "grad_diff_gap": max(grad_diff[n] / max(g[n], median, 1e-30)
                             for n in leaves),
    }

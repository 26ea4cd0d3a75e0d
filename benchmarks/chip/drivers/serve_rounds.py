"""Driver for streaming-serving traffic: closed rounds of batched requests.

Each round prefills ``batch`` prompts of ``prompt_len`` tokens, takes each
request's first token from the prefill's logits (greedy, as
``repro.serve.generate`` does) and then decodes one token per step until
every request has ``gen`` tokens, reading each step's tokens to the host
as a streaming server does.  The steps are the program's own,
``make_prefill_step`` and ``make_decode_step`` from ``repro.serve.step``.
Where the program gives a step as a plain function, the driver compiles
it with ``jax.jit``: run eagerly, the step traces and compiles its layer
scan again on every call, which no measured window may contain.  Prompts
come from the program's ``TokenPipeline`` seeded with ``--seed``, one
batch per round; weights from the configuration's reference
(``init_weights``) in one jitted call.

Set-up compiles both steps, prefills the first round and runs one decode
step whose result it drops.  After the window a round still open is
finished untimed if no round finished inside it; then requests drawn
from the seed out of the last finished round, one from each of
``check_requests`` equal stretches of its batch, are run through the
reference, and the widest gap by which a served token's reference logit
lies below the reference's best is compared with its limit.
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from harness.context import seed_key
from harness.lm import model_config, program_params
from harness.trace import span


def _compiled(step):
    return step if hasattr(step, "lower") else jax.jit(step)


class Driver:
    def __init__(self, ctx):
        from repro.data import DataConfig, TokenPipeline
        from repro.models import build_model
        from repro.serve.step import make_decode_step, make_prefill_step

        self.ctx = ctx
        cfg, tr = ctx.config, ctx.traffic
        self.B, self.P, self.G = tr["batch"], tr["prompt_len"], tr["gen"]
        self.ref = ctx.reference()
        self.key = seed_key(ctx.seed)
        model = build_model(model_config(cfg))
        with ctx.part("weights"):
            weights = jax.jit(functools.partial(self.ref.init_weights, cfg=cfg))(
                self.key)
            self.params = program_params(model, weights)
            del weights
        self.prefill = _compiled(make_prefill_step(model, max_len=self.P + self.G))
        self.decode = _compiled(make_decode_step(model, temperature=0.0))
        self.rng = jax.random.PRNGKey(0)      # unused by greedy decoding
        self.pipe = TokenPipeline(DataConfig(
            vocab=cfg["vocab_size"], seq_len=self.P, global_batch=self.B,
            seed=ctx.seed))
        self.round = -1
        self.finished = []                  # (prompts, served (B, G))
        self.w0 = float("inf")
        with ctx.part("first_prefill"):
            self._start_round()
        with ctx.part("warmup"):
            warm, _ = self.decode(self.params, self.tok, self.cache, self.rng)
            np.asarray(warm)
        self.begin()

    def begin(self):
        self.w0 = time.perf_counter()
        self.tokens = 0
        self.gaps = []
        self.ctx_lens = []
        self.units = 0
        self.rounds_touched = 1

    def _start_round(self):
        self.cache = None
        self.round += 1
        self.prompts = self.pipe.batch_at(self.round)["tokens"]
        with span("prefill"):
            logits, self.cache = self.prefill(
                self.params, {"tokens": jnp.asarray(self.prompts)})
            self.tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            self.served = [np.asarray(self.tok)]
        self.t_last = time.perf_counter()

    def _decode_step(self):
        with span("decode_step"):
            self.tok, self.cache = self.decode(self.params, self.tok,
                                               self.cache, self.rng)
            self.served.append(np.asarray(self.tok))
        t = time.perf_counter()
        if self.t_last >= self.w0:
            self.gaps.append(t - self.t_last)
        self.t_last = t

    def _round_done(self) -> bool:
        return len(self.served) == self.G

    def _finish_round(self):
        self.finished.append((self.prompts, np.stack(self.served, axis=1)))

    def unit(self):
        if self._round_done():
            self._finish_round()
            self._start_round()
            self.rounds_touched += 1
        else:
            # the step attends over the prompt and every token served so far
            self.ctx_lens.append(self.P + len(self.served))
            self._decode_step()
        self.tokens += self.B
        self.units += 1

    def facts(self, window_s: float):
        return {
            # requests: each round the window touched serves a batch
            "attempted": self.B * self.rounds_touched,
            "checked": len(self._check_rows()),
            "units": self.units,
            "end_to_end": {
                "decode_tokens_s": self.tokens / window_s,
                "tbt_p95_ms": 1e3 * float(np.percentile(self.gaps, 95)),
            },
            "decode_batch": self.B,
            "decode_ctx_lens": list(self.ctx_lens),
            "window_s": window_s,
        }

    def release(self):
        if not self.finished:
            while not self._round_done():
                self._decode_step()
            self._finish_round()
        self.params = self.cache = self.tok = None
        self.prefill = self.decode = None

    def _check_rows(self):
        """``check_requests`` rows of the batch drawn from the seed, one
        from each of as many equal stretches of the batch."""
        rng = np.random.default_rng(self.ctx.seed)
        ends = np.linspace(0, self.B, self.ctx.traffic["check_requests"] + 1)
        ends = ends.astype(int)
        return [int(rng.integers(lo, hi))
                for lo, hi in zip(ends[:-1], ends[1:]) if hi > lo]

    def _gaps(self, quant=None):
        cfg = self.ctx.config
        prompts, served = self.finished[-1]
        rows = self._check_rows()
        w = jax.jit(functools.partial(self.ref.init_weights, cfg=cfg))(self.key)
        gap_fn = jax.jit(functools.partial(self.ref.served_gap, first=self.P - 1,
                                           cfg=cfg, quant=quant))
        gaps = []
        with span("reference"):
            for r in rows:
                seq = np.concatenate([prompts[r], served[r, :-1]])[None]
                gaps.append(float(gap_fn(w, jnp.asarray(seq),
                                         served=jnp.asarray(served[r]))))
        return max(gaps)

    def checks(self):
        return {"logit_gap": {"value": self._gaps(),
                              "limit": self.ctx.traffic["limits"]["logit_gap"]}}

    def control(self):
        """At the same prompts and served tokens, the gap of the tokens
        that the fp8 reference puts first."""
        return {"control": {"logit_gap": self._gaps(quant="fp8")}}

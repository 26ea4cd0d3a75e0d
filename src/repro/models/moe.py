"""Mixture-of-Experts FFN: top-k router + two dispatch implementations.

``apply_moe_dense``
    one-hot einsum dispatch — the *reference semantics* (exact token
    choice, no capacity drops).  Used by smoke tests and as the oracle
    for the distributed path.

``apply_moe_sharded``
    the production path, shard_map over (ep_axis, tp_axis):

      route locally -> capacity-bounded scatter into an (E, cap, D)
      dispatch buffer -> ``all_to_all`` over the expert-parallel axis
      (tokens travel to the data-shard that owns their expert) ->
      ``all_gather`` the expert's token set over the tensor axis ->
      local grouped GEMM with (E/ep, D, F/tp) weight shards ->
      ``reduce_scatter`` the partial outputs back over the tensor axis
      -> ``all_to_all`` home -> weighted combine.

    This is the paper's "shuffle" at mesh granularity (DESIGN.md §5): a
    *provable* token route over the interconnect replaces the all-gather
    of expert weights a naive sharded einsum would emit — the same
    replace-redundant-memory-traffic-with-point-to-point-communication
    move the warp shuffle makes inside an SM.

Equivalence: sharded == dense whenever no expert exceeds capacity
(property-tested in tests/test_distributed.py with capacity_factor=E/k).
"""

from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from .common import EMBED, EXPERT, FF, Params, dense_init, larray


def init_moe(key, d_model: int, d_ff: int, n_experts: int, top_k: int,
             dtype=jnp.float32) -> Params:
    ks = jax.random.split(key, 4)
    return {
        "router": larray(dense_init(ks[0], (d_model, n_experts),
                                    dtype=jnp.float32), EMBED, EXPERT),
        "w_gate": larray(dense_init(ks[1], (n_experts, d_model, d_ff), in_axis=1,
                                    dtype=dtype), EXPERT, EMBED, FF),
        "w_up": larray(dense_init(ks[2], (n_experts, d_model, d_ff), in_axis=1,
                                  dtype=dtype), EXPERT, EMBED, FF),
        "w_down": larray(dense_init(ks[3], (n_experts, d_ff, d_model), in_axis=1,
                                    dtype=dtype), EXPERT, FF, EMBED),
    }


def router_probs(router: jnp.ndarray, x: jnp.ndarray, top_k: int):
    """x: (..., D).  Returns (indices (..., k), weights (..., k), logits)."""
    logits = jnp.einsum("...d,de->...e", x.astype(jnp.float32),
                        router.astype(jnp.float32))
    weights, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return idx, weights.astype(x.dtype), logits


def aux_load_balance_loss(logits: jnp.ndarray, idx: jnp.ndarray,
                          n_experts: int) -> jnp.ndarray:
    """Switch-style load-balancing auxiliary loss."""
    probs = jax.nn.softmax(logits, axis=-1)
    me = jnp.mean(probs.reshape(-1, n_experts), axis=0)
    ce = jnp.mean(
        (jax.nn.one_hot(idx.reshape(-1, idx.shape[-1]), n_experts).sum(1) > 0
         ).astype(jnp.float32), axis=0)
    return n_experts * jnp.sum(me * ce)


def _expert_ffn(w_gate, w_up, w_down, x):
    """x: (E, T, D) grouped tokens -> (E, T, D) (or partial over sharded F)."""
    g = jnp.einsum("etd,edf->etf", x, w_gate)
    u = jnp.einsum("etd,edf->etf", x, w_up)
    h = jax.nn.silu(g) * u
    return jnp.einsum("etf,efd->etd", h, w_down)


# ---------------------------------------------------------------------------
# dense (reference) dispatch
# ---------------------------------------------------------------------------

def apply_moe_dense(params: Params, x: jnp.ndarray, top_k: int,
                    n_experts: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Exact one-hot dispatch, no drops.  x: (B, S, D) -> (y, aux)."""
    B, S, D = x.shape
    idx, w, logits = router_probs(params["router"], x, top_k)     # (B,S,k)
    onehot = jax.nn.one_hot(idx, n_experts, dtype=x.dtype)        # (B,S,k,E)
    combine = jnp.einsum("bske,bsk->bse", onehot, w)              # (B,S,E)
    mask = (combine != 0).astype(x.dtype)
    xe = jnp.einsum("bsd,bse->ebsd", x, mask)
    ye = _expert_ffn(params["w_gate"], params["w_up"], params["w_down"],
                     xe.reshape(n_experts, B * S, D))
    y = jnp.einsum("ebsd,bse->bsd", ye.reshape(n_experts, B, S, D), combine)
    return y, aux_load_balance_loss(logits, idx, n_experts)


# ---------------------------------------------------------------------------
# sharded (production) dispatch
# ---------------------------------------------------------------------------

def choose_schedule(n_experts: int, d_model: int, d_ff: int, mesh,
                    ep_axis: str = "data", tp_axis: str = "model",
                    budget_bytes: int = 64 * 2**20) -> str:
    """Pick the dispatch schedule (see apply_moe_sharded / _ep_tp).

    ``ep_tp`` (experts sharded over the tensor axis, full-width FFN, no
    token all-gather) wins when the per-device expert weights it implies
    — total expert params / |tp|, replicated over the data axis — fit a
    modest budget.  Small-expert models (granite: 6 MB/layer) qualify;
    kimi-k2 (2.1 GB/layer) must keep the 2D schedule.
    """
    tp = mesh.shape.get(tp_axis, 1)
    if n_experts % tp == 0:
        per_dev = 3 * n_experts * d_model * d_ff * 2 // tp
        if per_dev <= budget_bytes:
            return "ep_tp"
    # F-sharding gathers each expert's token set over the tensor axis;
    # when experts are narrower than d_model, D-sharding dispatches D/tp
    # slices and psums only the (tokens, F) hidden instead (§Perf round
    # 3: kimi collective term -35%).
    if d_ff < d_model and d_model % tp == 0:
        return "2d_dshard"
    return "2d"


def apply_moe_sharded(params: Params, x: jnp.ndarray, top_k: int,
                      n_experts: int, mesh, ep_axis: str = "data",
                      tp_axis: str = "model",
                      capacity_factor: float = 1.25,
                      batch_spec=None, schedule: str = "auto"):
    """2D expert + tensor parallel dispatch.  x: (B, S, D).

    Sharding contract (resharded at the shard_map boundary by GSPMD):
      x         (B/ep, S/tp, D)    batch over ep, sequence over tp
      w_gate/up (E/ep, D, F/tp)
      w_down    (E/ep, F/tp, D)
      router    replicated
    """
    if schedule == "auto":
        schedule = choose_schedule(n_experts, x.shape[-1],
                                   params["w_gate"].shape[-1], mesh,
                                   ep_axis, tp_axis)
    if schedule == "ep_tp":
        return _apply_moe_ep_tp(params, x, top_k, n_experts, mesh,
                                ep_axis, tp_axis, capacity_factor,
                                batch_spec)
    if schedule == "2d_dshard":
        return _apply_moe_2d_dshard(params, x, top_k, n_experts, mesh,
                                    ep_axis, tp_axis, capacity_factor,
                                    batch_spec)
    ep = mesh.shape[ep_axis]
    tp = mesh.shape[tp_axis]
    assert n_experts % ep == 0, (n_experts, ep)
    e_local = n_experts // ep
    if batch_spec is None:
        # multi-pod: batch is additionally DP-sharded over the pod axis;
        # experts stay replicated across pods (all_to_all is intra-pod).
        batch_spec = (("pod", ep_axis) if "pod" in mesh.shape else ep_axis)
    # decode (S=1) and short sequences cannot shard S over the tensor axis
    seq_spec = tp_axis if x.shape[1] % tp == 0 else None
    bsz = 1
    for a in ((batch_spec,) if isinstance(batch_spec, str) else batch_spec):
        bsz *= mesh.shape[a]
    if x.shape[0] % bsz != 0:
        batch_spec = None

    def local_fn(router, w_gate, w_up, w_down, xs):
        Bl, Sl, D = xs.shape
        T = Bl * Sl
        xf = xs.reshape(T, D)
        idx, w, logits = router_probs(router, xf, top_k)          # (T,k)
        cap = max(4, math.ceil(capacity_factor * top_k * T / n_experts))
        flat_e = idx.reshape(-1)                                  # (T*k,)
        onehot = jax.nn.one_hot(flat_e, n_experts, dtype=jnp.int32)
        slot = jnp.sum(jnp.cumsum(onehot, axis=0) * onehot, axis=-1) - 1
        keep = slot < cap
        tok_ids = jnp.repeat(jnp.arange(T), top_k)
        buf = jnp.zeros((n_experts, cap, D), xf.dtype)
        buf = buf.at[flat_e, jnp.clip(slot, 0, cap - 1)].add(
            jnp.where(keep[:, None], xf[tok_ids], 0))
        # --- dispatch: tokens travel to their expert's ep shard ---------
        buf = buf.reshape(ep, e_local, cap, D)
        recv = jax.lax.all_to_all(buf, ep_axis, 0, 0, tiled=False)
        toks = recv.transpose(1, 0, 2, 3).reshape(e_local, ep * cap, D)
        # --- tensor-parallel expert FFN ----------------------------------
        # gather every tp column's token set; each column holds an F/tp
        # weight shard, computes a partial output, and reduce-scatter
        # returns the summed result for its own tokens.
        toks_all = jax.lax.all_gather(toks, tp_axis, axis=1, tiled=True)
        part = _expert_ffn(w_gate, w_up, w_down, toks_all)
        ye = jax.lax.psum_scatter(part, tp_axis, scatter_dimension=1,
                                  tiled=True)                    # (e_l, ep*cap, D)
        # --- return trip --------------------------------------------------
        ye = ye.reshape(e_local, ep, cap, D).transpose(1, 0, 2, 3)
        back = jax.lax.all_to_all(ye, ep_axis, 0, 0, tiled=False)
        back = back.reshape(n_experts, cap, D)
        gathered = back[flat_e, jnp.clip(slot, 0, cap - 1)]
        gathered = jnp.where(keep[:, None], gathered, 0)
        y = jnp.zeros((T, D), xs.dtype).at[tok_ids].add(
            gathered * w.reshape(-1)[:, None])
        aux = aux_load_balance_loss(logits, idx, n_experts)
        aux = jax.lax.pmean(jax.lax.pmean(aux, ep_axis), tp_axis)
        return y.reshape(Bl, Sl, D), aux

    in_specs = (
        P(),                                    # router
        P(ep_axis, None, tp_axis),              # w_gate
        P(ep_axis, None, tp_axis),              # w_up
        P(ep_axis, tp_axis, None),              # w_down
        P(batch_spec, seq_spec, None),          # tokens
    )
    out_specs = (P(batch_spec, seq_spec, None), P())
    fn = shard_map(local_fn, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    return fn(params["router"], params["w_gate"], params["w_up"],
              params["w_down"], x)


# ---------------------------------------------------------------------------
# ep_tp schedule: experts sharded over the TENSOR axis (full-width FFN)
# ---------------------------------------------------------------------------

def _apply_moe_ep_tp(params: Params, x: jnp.ndarray, top_k: int,
                     n_experts: int, mesh, ep_axis: str, tp_axis: str,
                     capacity_factor: float, batch_spec):
    """Beyond-paper schedule for small-expert MoEs (§Perf hillclimb).

    Experts live whole (full d_ff) on tensor-axis shards, replicated
    over the data axis; tokens are sharded (batch over data/pod,
    sequence over the tensor axis) and travel by ONE ``all_to_all`` over
    the tensor axis — the per-expert all_gather / reduce_scatter pair of
    the 2D schedule disappears entirely.  Expert grads all-reduce over
    the data axis like any replicated parameter.
    """
    tp = mesh.shape[tp_axis]
    assert n_experts % tp == 0
    e_local = n_experts // tp
    if batch_spec is None:
        batch_spec = (("pod", ep_axis) if "pod" in mesh.shape else ep_axis)
    seq_spec = tp_axis if x.shape[1] % tp == 0 else None
    bsz = 1
    for a in ((batch_spec,) if isinstance(batch_spec, str) else batch_spec):
        bsz *= mesh.shape[a]
    if x.shape[0] % bsz != 0:
        batch_spec = None

    def local_fn(router, w_gate, w_up, w_down, xs):
        Bl, Sl, D = xs.shape
        T = Bl * Sl
        xf = xs.reshape(T, D)
        idx, w, logits = router_probs(router, xf, top_k)
        cap = max(4, math.ceil(capacity_factor * top_k * T / n_experts))
        flat_e = idx.reshape(-1)
        onehot = jax.nn.one_hot(flat_e, n_experts, dtype=jnp.int32)
        slot = jnp.sum(jnp.cumsum(onehot, axis=0) * onehot, axis=-1) - 1
        keep = slot < cap
        tok_ids = jnp.repeat(jnp.arange(T), top_k)
        buf = jnp.zeros((n_experts, cap, D), xf.dtype)
        buf = buf.at[flat_e, jnp.clip(slot, 0, cap - 1)].add(
            jnp.where(keep[:, None], xf[tok_ids], 0))
        # ONE hop: tokens to the tensor-axis shard owning their expert
        buf = buf.reshape(tp, e_local, cap, D)
        recv = jax.lax.all_to_all(buf, tp_axis, 0, 0, tiled=False)
        toks = recv.transpose(1, 0, 2, 3).reshape(e_local, tp * cap, D)
        ye = _expert_ffn(w_gate, w_up, w_down, toks)     # full-width FFN
        ye = ye.reshape(e_local, tp, cap, D).transpose(1, 0, 2, 3)
        back = jax.lax.all_to_all(ye, tp_axis, 0, 0, tiled=False)
        back = back.reshape(n_experts, cap, D)
        gathered = back[flat_e, jnp.clip(slot, 0, cap - 1)]
        gathered = jnp.where(keep[:, None], gathered, 0)
        y = jnp.zeros((T, D), xs.dtype).at[tok_ids].add(
            gathered * w.reshape(-1)[:, None])
        aux = aux_load_balance_loss(logits, idx, n_experts)
        aux = jax.lax.pmean(jax.lax.pmean(aux, ep_axis), tp_axis)
        return y.reshape(Bl, Sl, D), aux

    in_specs = (
        P(),
        P(tp_axis, None, None),       # whole experts on tensor shards
        P(tp_axis, None, None),
        P(tp_axis, None, None),
        P(batch_spec, seq_spec, None),
    )
    out_specs = (P(batch_spec, seq_spec, None), P())
    fn = shard_map(local_fn, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    return fn(params["router"], params["w_gate"], params["w_up"],
              params["w_down"], x)


# ---------------------------------------------------------------------------
# 2d_dshard schedule: expert D sharded over the tensor axis (kimi-class)
# ---------------------------------------------------------------------------

def _apply_moe_2d_dshard(params: Params, x: jnp.ndarray, top_k: int,
                         n_experts: int, mesh, ep_axis: str, tp_axis: str,
                         capacity_factor: float, batch_spec):
    """§Perf round 3: for MoEs whose per-expert width is SMALLER than
    d_model (kimi: F=2048 vs D=7168), sharding the expert weights'
    **D dim** over the tensor axis beats F-sharding: dispatch buffers
    carry D/tp slices (no token all_gather over the tensor axis at all)
    and the only tensor-axis collective is a psum of the (tokens, F)
    hidden — F/D times smaller than the gathered token set.

      x        (B/ep, S, D/tp)   — D sharded for dispatch
      w_gate/up (E/ep, D/tp, F)
      w_down    (E/ep, F, D/tp)
      router    (D/tp, E)        — partial logits psum'd over tp
    """
    ep = mesh.shape[ep_axis]
    tp = mesh.shape[tp_axis]
    assert n_experts % ep == 0
    e_local = n_experts // ep
    if batch_spec is None:
        batch_spec = (("pod", ep_axis) if "pod" in mesh.shape else ep_axis)
    bsz = 1
    for a in ((batch_spec,) if isinstance(batch_spec, str) else batch_spec):
        bsz *= mesh.shape[a]
    if x.shape[0] % bsz != 0:
        batch_spec = None

    def local_fn(router, w_gate, w_up, w_down, xs):
        Bl, Sl, Dl = xs.shape
        T = Bl * Sl
        xf = xs.reshape(T, Dl)
        # routing on D-shards: partial logits, exact after psum
        logits = jnp.einsum("td,de->te", xf.astype(jnp.float32),
                            router.astype(jnp.float32))
        logits = jax.lax.psum(logits, tp_axis)
        weights, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
        weights = (weights / jnp.sum(weights, -1, keepdims=True)).astype(
            xs.dtype)
        cap = max(4, math.ceil(capacity_factor * top_k * T / n_experts))
        flat_e = idx.reshape(-1)
        onehot = jax.nn.one_hot(flat_e, n_experts, dtype=jnp.int32)
        slot = jnp.sum(jnp.cumsum(onehot, axis=0) * onehot, axis=-1) - 1
        keep = slot < cap
        tok_ids = jnp.repeat(jnp.arange(T), top_k)
        buf = jnp.zeros((n_experts, cap, Dl), xf.dtype)
        buf = buf.at[flat_e, jnp.clip(slot, 0, cap - 1)].add(
            jnp.where(keep[:, None], xf[tok_ids], 0))
        buf = buf.reshape(ep, e_local, cap, Dl)
        recv = jax.lax.all_to_all(buf, ep_axis, 0, 0, tiled=False)
        toks = recv.transpose(1, 0, 2, 3).reshape(e_local, ep * cap, Dl)
        # expert FFN: D-partial gate/up -> psum over tp -> full-F hidden
        g = jnp.einsum("etd,edf->etf", toks, w_gate)
        u = jnp.einsum("etd,edf->etf", toks, w_up)
        g = jax.lax.psum(g, tp_axis)
        u = jax.lax.psum(u, tp_axis)
        h = jax.nn.silu(g) * u
        ye = jnp.einsum("etf,efd->etd", h, w_down)       # (e_l, T', D/tp)
        ye = ye.reshape(e_local, ep, cap, Dl).transpose(1, 0, 2, 3)
        back = jax.lax.all_to_all(ye, ep_axis, 0, 0, tiled=False)
        back = back.reshape(n_experts, cap, Dl)
        gathered = back[flat_e, jnp.clip(slot, 0, cap - 1)]
        gathered = jnp.where(keep[:, None], gathered, 0)
        y = jnp.zeros((T, Dl), xs.dtype).at[tok_ids].add(
            gathered * weights.reshape(-1)[:, None])
        aux = aux_load_balance_loss(logits, idx, n_experts)
        aux = jax.lax.pmean(jax.lax.pmean(aux, ep_axis), tp_axis)
        return y.reshape(Bl, Sl, Dl), aux

    in_specs = (
        P(tp_axis, None),                      # router D-sharded
        P(ep_axis, tp_axis, None),             # w_gate (E/ep, D/tp, F)
        P(ep_axis, tp_axis, None),             # w_up
        P(ep_axis, None, tp_axis),             # w_down (E/ep, F, D/tp)
        P(batch_spec, None, tp_axis),          # tokens D-sharded
    )
    out_specs = (P(batch_spec, None, tp_axis), P())
    fn = shard_map(local_fn, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    return fn(params["router"], params["w_gate"], params["w_up"],
              params["w_down"], x)

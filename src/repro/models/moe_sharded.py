"""Explicitly-partitioned MoE dispatch (shard_map), replacing GSPMD's
auto-partition of the dispatch scatter.

Why: the dense-path scatter ``zeros[E*C, d].at[slot].set(rows)`` with
runtime indices makes XLA's SPMD partitioner fall back to replicating the
updates — an all-gather of [N*k, d] (224 GiB/device for kimi-k2
prefill). The fix is the classic GShard schedule, written explicitly:

train/prefill (tokens sharded over dp x tp via sequence parallelism):
  1. local top-k routing + local capacity-C dispatch (tiny local scatter)
  2. all_to_all over the EP axis ("model"): bring each expert's rows to
     its owner — [tp, E_loc, C, d] exchange, no replication anywhere
  3. expert FFN on [E_loc, tp*C, d]
  4. all_to_all back + local gate-weighted combine

decode (few tokens, replicated over the model axis):
  each EP rank computes only its own experts' contributions for the
  (replicated) tokens and the combine is a psum over the model axis —
  cheaper than an a2a round-trip for O(batch) tokens.

The load-balance aux loss is computed per shard and pmean'd — an
expectation-level approximation of the global Switch aux (exact when
shards are identically distributed); documented in tests/spmd.

This mirrors TAM's design point: group-by-destination locally, then one
aggregated exchange on the contended axis (cf. core/exchange.py).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.models.config import ModelConfig
from repro.models.sharding import ShardingPlan


def _route(xt, router, k):
    """Local routing: returns (gates [n,k] f32, eids [n,k] i32, probs)."""
    logits = xt.astype(jnp.float32) @ router
    probs = jax.nn.softmax(logits, axis=-1)
    gates, eids = lax.top_k(probs, k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return gates, eids.astype(jnp.int32), probs


def _local_dispatch(xt, eids, e, cap):
    """Scatter local tokens into [e, cap, d] expert buckets.

    Returns (disp, slot_of_row [n*k] — destination slot or e*cap when
    dropped). Same group-by-destination primitive as TAM bucketing.
    """
    n, d = xt.shape
    k = eids.shape[-1]
    flat_e = eids.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    ranked = flat_e[order]
    counts = jnp.zeros((e,), jnp.int32).at[flat_e].add(1)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(counts)[:-1].astype(jnp.int32)])
    pos = jnp.arange(n * k, dtype=jnp.int32) - starts[ranked]
    ok = pos < cap
    slot_sorted = jnp.where(ok, ranked * cap + pos, e * cap)
    slot_of_row = jnp.zeros((n * k,), jnp.int32).at[order].set(slot_sorted)
    token_of = order // k
    disp = jnp.zeros((e * cap, d), xt.dtype).at[slot_sorted].set(
        xt[token_of], mode="drop")
    return disp.reshape(e, cap, d), slot_of_row


def _expert_ffn(disp, wi, wg, wo):
    h = jnp.einsum("ecd,edf->ecf", disp, wi)
    g = jnp.einsum("ecd,edf->ecf", disp, wg)
    return jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * h, wo)


def _combine(eo_flat, slot_of_row, gates, n, k, d):
    eo_pad = jnp.concatenate(
        [eo_flat, jnp.zeros((1, d), eo_flat.dtype)], axis=0)
    sentinel = eo_flat.shape[0]
    per = eo_pad[jnp.minimum(slot_of_row, sentinel)].reshape(n, k, d)
    return (per * gates[..., None].astype(per.dtype)).sum(axis=1)


def _aux_loss(probs, eids, e, n, k):
    me = probs.mean(axis=0)
    ce = jnp.zeros((e,), jnp.float32).at[eids.reshape(-1)].add(
        1.0 / (n * k))
    return e * jnp.sum(me * ce)


def moe_sharded(p: dict, x: jax.Array, cfg: ModelConfig,
                plan: ShardingPlan):
    """shard_map MoE for a mesh'd plan. Returns (out, aux)."""
    m = cfg.moe
    e, k = m.num_experts, m.top_k
    mesh = plan.mesh
    tp = plan.tp
    dp_axes = tuple(plan.data_axes)
    all_axes = dp_axes + (tp,)
    ntp = mesh.shape[tp]
    e_loc = e // ntp
    b, s, d = x.shape

    if plan.shard_seq:
        x_spec = P(plan.dp, tp, None)
        n_loc = (b // math.prod(mesh.shape[a] for a in dp_axes)) * (s // ntp)
    else:
        x_spec = P(plan.dp, None, None)
        n_loc = (b // math.prod(mesh.shape[a] for a in dp_axes)) * s
    cap = max(4, -(-int(n_loc * k / e * m.capacity_factor) // 4) * 4)

    w_spec = P(tp, None, None)     # dp (FSDP) shards gathered at entry
    r_spec = P(None, None)

    if plan.shard_seq:
        def fn(xl, router, wi, wg, wo):
            bl, sl, _ = xl.shape
            n = bl * sl
            xt = xl.reshape(n, d)
            gates, eids, probs = _route(xt, router, k)
            disp, slot_of_row = _local_dispatch(xt, eids, e, cap)
            # EP exchange: [tp_dest, e_loc, cap, d] -> rows at owners
            disp = disp.reshape(ntp, e_loc, cap, d)
            rx = lax.all_to_all(disp, tp, split_axis=0, concat_axis=0,
                                tiled=True)              # [tp_src, e_loc, cap, d]
            rows = rx.transpose(1, 0, 2, 3).reshape(e_loc, ntp * cap, d)
            eo = _expert_ffn(rows, wi, wg, wo)
            back = eo.reshape(e_loc, ntp, cap, d).transpose(1, 0, 2, 3)
            tx = lax.all_to_all(back, tp, split_axis=0, concat_axis=0,
                                tiled=True)              # [tp_dest->me]
            eo_flat = tx.reshape(e * cap, d)
            y = _combine(eo_flat, slot_of_row, gates, n, k, d)
            aux = lax.pmean(_aux_loss(probs, eids, e, n, k), all_axes)
            return y.reshape(bl, sl, d), aux
    else:
        def fn(xl, router, wi, wg, wo):
            bl, sl, _ = xl.shape
            n = bl * sl
            xt = xl.reshape(n, d)
            gates, eids, probs = _route(xt, router, k)
            my_tp = lax.axis_index(tp)
            local_eids = eids - my_tp * e_loc
            mine = (local_eids >= 0) & (local_eids < e_loc)
            masked_gates = jnp.where(mine, gates, 0.0)
            safe_eids = jnp.where(mine, local_eids, 0)
            disp, slot_of_row = _local_dispatch(xt, safe_eids, e_loc, cap)
            eo = _expert_ffn(disp, wi, wg, wo)
            y = _combine(eo.reshape(e_loc * cap, d), slot_of_row,
                         masked_gates, n, k, d)
            y = lax.psum(y, tp)
            aux = _aux_loss(probs, eids, e, n, k)
            if dp_axes:
                aux = lax.pmean(aux, dp_axes)
            return y.reshape(bl, sl, d), aux

    out, aux = jax.shard_map(
        fn, mesh=mesh, check_vma=False,
        in_specs=(x_spec, r_spec, w_spec, w_spec, w_spec),
        out_specs=(x_spec, P()),
    )(x, p["router"], p["wi"], p["wg"], p["wo"])
    return out, aux

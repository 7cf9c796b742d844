"""GPipe-style pipeline parallelism as a composable shard_map transform.

Optional fourth parallelism axis ("pipe"): the layer stack is split into
S stages along the scanned n_blocks dimension; microbatches stream
through stages with ``ppermute`` handoffs. S + M - 1 rotations for M
microbatches (classic GPipe bubble = (S-1)/(S+M-1)).

This is deliberately independent of the main GSPMD path: you wrap a
per-stage apply function; weights arrive stage-sharded via in_specs.
Used by tests/test_pipeline.py and available to launch configs that set
``pipeline_stages > 1``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P



def pipeline_apply(stage_fn, mesh, *, axis: str = "pipe",
                   microbatches: int):
    """Build fn(stage_params, x) -> y running the S-stage pipeline.

    stage_fn(params_slice, x_mb) applies ONE stage to ONE microbatch.
    stage_params: pytree with leading dim S (stage-sharded over ``axis``).
    x: [M * mb, ...] global batch, sharded over ``axis`` on dim 0 only
    for transport convenience (microbatches round-robin the stages).
    """
    S = mesh.shape[axis]

    def shard_fn(params, x):
        # params leaves: [1, ...] local stage slice; x local [M*mb/S, ...]
        p_local = jax.tree.map(lambda a: a[0], params)
        stage = lax.axis_index(axis)
        m_total = microbatches
        # gather the full batch once (stage 0 owns input semantics; other
        # stages receive via rotation, but SPMD needs identical shapes)
        x_all = lax.all_gather(x, axis, axis=0, tiled=True)
        mbs = x_all.shape[0] // m_total
        rounds = S + m_total - 1

        def body(carry, t):
            acts, outs = carry
            # stage s works on microbatch (t - s) if 0 <= t - s < M
            mb_idx = t - stage
            active = (mb_idx >= 0) & (mb_idx < m_total)
            take = jnp.clip(mb_idx, 0, m_total - 1)
            x_in = lax.cond(
                stage == 0,
                lambda: lax.dynamic_slice_in_dim(x_all, take * mbs, mbs, 0),
                lambda: acts)
            y = stage_fn(p_local, x_in)
            y = jnp.where(active, y, acts)
            # hand activations to the next stage
            nxt = lax.ppermute(y, axis,
                               [(i, (i + 1) % S) for i in range(S)])
            # last stage emits completed microbatches
            done_idx = t - (S - 1)
            emit = (stage == S - 1) & (done_idx >= 0) & (done_idx < m_total)
            outs = lax.cond(
                emit,
                lambda o: lax.dynamic_update_slice_in_dim(
                    o, y, jnp.clip(done_idx, 0, m_total - 1) * mbs, 0),
                lambda o: o, outs)
            return (nxt, outs), None

        acts0 = jnp.zeros_like(x_all[:mbs])
        outs0 = jnp.zeros_like(x_all)
        (_, outs), _ = lax.scan(body, (acts0, outs0),
                                jnp.arange(rounds))
        # results live on the last stage; broadcast and reslice
        outs = lax.psum(
            jnp.where(stage == S - 1, outs, jnp.zeros_like(outs)), axis)
        mine = lax.dynamic_slice_in_dim(
            outs, lax.axis_index(axis) * (outs.shape[0] // S),
            outs.shape[0] // S, 0)
        return mine

    return jax.shard_map(
        shard_fn, mesh=mesh, check_vma=False,
        in_specs=(P(axis), P(axis)),
        out_specs=P(axis))

"""Pallas TPU kernel: coalesce adjacent contiguous requests.

Given an offset-sorted request block, fuse every run of contiguous
requests (``offset[i] + length[i] == offset[i+1]``) into one request and
compact the results to the front of the block. This is the aggregator
step that lets TAM forward far fewer offset-length pairs across the slow
axis (BTIO coalesces 1.34e9 -> 2.36e7 requests at 256 nodes in the
paper).

TPU shape: boundary detection is an elementwise shift-compare; run ids
and compaction positions are prefix sums (log2(n) doubling sweeps on the
VPU); the head-offset/segment-length reductions become masked selects
plus a segment-sum implemented with the same prefix-sum trick — all on a
VMEM-resident block, no scalar loops.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.requests import PAD_OFFSET
from repro.kernels import interpret_mode

MAX_BLOCK = 32768


def _prefix_sum(x: jax.Array) -> jax.Array:
    """Hillis-Steele inclusive scan: log2(n) shifted adds (VPU-friendly)."""
    n = x.shape[0]
    d = 1
    while d < n:
        shifted = jnp.pad(x, (d, 0))[:n]
        x = x + shifted
        d *= 2
    return x


def _coalesce_block(off: jax.Array, ln: jax.Array):
    n = off.shape[0]
    prev_end = jnp.pad(off + ln, (1, 0), constant_values=-1)[:n]
    is_pad = off == PAD_OFFSET
    boundary = (off != prev_end) | is_pad
    # run id of each request (0-based), padding runs included then masked
    run = _prefix_sum(boundary.astype(jnp.int32)) - 1
    # head of each valid run carries the coalesced offset; the coalesced
    # length of a run is the inclusive-scan of lengths at the run's LAST
    # element minus the exclusive prefix before its head.
    csum = _prefix_sum(jnp.where(is_pad, 0, ln))
    is_head = boundary & ~is_pad
    next_boundary = jnp.pad(boundary, (0, 1), constant_values=True)[1:]
    is_last = next_boundary & ~is_pad
    head_excl = csum - jnp.where(is_pad, 0, ln)   # prefix before me
    # scatter head offset / head prefix / last csum into run slots
    sentinel = n  # positive OOB => dropped (never wrap with -1)
    head_idx = jnp.where(is_head, run, sentinel)
    last_idx = jnp.where(is_last, run, sentinel)
    run_off = jnp.full((n,), PAD_OFFSET, jnp.int32).at[head_idx].set(
        off, mode="drop")
    run_start = jnp.zeros((n,), jnp.int32).at[head_idx].set(
        head_excl, mode="drop")
    run_end = jnp.zeros((n,), jnp.int32).at[last_idx].set(csum, mode="drop")
    n_runs = jnp.sum(is_head.astype(jnp.int32))
    i = jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0).reshape(n)
    valid = i < n_runs
    return (jnp.where(valid, run_off, PAD_OFFSET),
            jnp.where(valid, run_end - run_start, 0),
            n_runs)


@functools.partial(jax.jit, static_argnames=("interpret",))
def coalesce(offsets: jax.Array, lengths: jax.Array, *,
             interpret: bool | None = None):
    """Coalesce a batch of sorted request blocks.

    offsets/lengths: int32[b, n], offset-sorted with PAD_OFFSET padding
    (interspersed padding allowed only at the tail, i.e. post-sort).
    Returns (offsets, lengths, counts): compacted runs per block.
    """
    b, n = offsets.shape
    if n > MAX_BLOCK:
        raise ValueError(f"block length {n} > {MAX_BLOCK}")
    block = pl.BlockSpec((1, n), lambda i: (i, 0))
    cnt_spec = pl.BlockSpec((1,), lambda i: (i,))

    def kernel(o, l, oo, lo, co):
        off, ln, cnt = _coalesce_block(o[0, :], l[0, :])
        oo[0, :] = off
        lo[0, :] = ln
        co[0] = cnt

    out_shape = [jax.ShapeDtypeStruct((b, n), jnp.int32),
                 jax.ShapeDtypeStruct((b, n), jnp.int32),
                 jax.ShapeDtypeStruct((b,), jnp.int32)]
    return pl.pallas_call(
        kernel,
        grid=(b,),
        in_specs=[block, block],
        out_specs=[block, block, cnt_spec],
        out_shape=out_shape,
        interpret=interpret_mode(interpret),
    )(offsets, lengths)

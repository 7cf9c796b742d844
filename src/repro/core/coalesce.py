"""Sort + coalesce of offset-length request lists (pure-jnp path).

This is the algorithmic heart of the paper's aggregation layers: each
(local or global) aggregator merge-sorts the offset-length pairs gathered
from its senders and coalesces consecutive contiguous pairs
(``offset[i] + length[i] == offset[i+1]``) into single larger requests.
Block-partitioned patterns (BTIO, S3D-IO) coalesce by up to
``(1/2)^(P/P_L)`` — the coalesce ratio is what makes TAM's inter-node
phase cheap.

The Pallas kernels in ``repro.kernels`` provide the TPU-optimized
implementations of the same operations; this module is the oracle and
the portable fallback.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.requests import (PAD_OFFSET, RequestList,
                                 exclusive_prefix_sum, mask_invalid,
                                 prefix_sum, segment_ids)


def sort_requests(r: RequestList) -> RequestList:
    """Sort requests by offset (padding sorts to the end).

    The MPI analogue is the heap merge-sort over per-sender pre-sorted
    lists; a single key sort is the TPU-native equivalent (and is what
    the bitonic Pallas kernel implements).
    """
    r = mask_invalid(r)
    order = jnp.argsort(r.offsets, stable=True)
    return RequestList(r.offsets[order], r.lengths[order], r.count)


def merge_sorted(lists: RequestList) -> RequestList:
    """Merge a batch of per-sender sorted lists into one sorted list.

    ``lists`` is a RequestList with leading batch dim [S, cap]; returns a
    flat sorted RequestList of capacity S*cap. This is the aggregator-side
    merge in both aggregation layers.
    """
    off = lists.offsets.reshape(-1)
    ln = lists.lengths.reshape(-1)
    cnt = jnp.sum(lists.count, dtype=jnp.int32)
    return sort_requests(RequestList(off, ln, cnt))


def coalesce_sorted(r: RequestList) -> RequestList:
    """Coalesce adjacent contiguous requests of an offset-sorted list.

    Returns a compacted RequestList (valid entries at the front) with
    the same capacity. Zero-length requests must not appear among the
    valid entries (the padding convention reserves length 0).
    """
    off, ln = r.offsets, r.lengths
    cap = r.capacity
    prev_end = jnp.concatenate([jnp.full((1,), -1, jnp.int32),
                                (off + ln)[:-1]])
    is_pad = off == PAD_OFFSET
    # a new segment starts where the request is not contiguous with the
    # previous one; padding always starts its own (discarded) segment.
    boundary = (off != prev_end) | is_pad
    seg = prefix_sum(boundary) - 1
    seg_off = jax.ops.segment_min(jnp.where(is_pad, PAD_OFFSET, off), seg,
                                  num_segments=cap)
    seg_len = jax.ops.segment_sum(jnp.where(is_pad, 0, ln), seg,
                                  num_segments=cap)
    n_seg = jnp.where(r.count > 0, seg[jnp.maximum(r.count - 1, 0)] + 1, 0)
    idx = jnp.arange(cap, dtype=jnp.int32)
    valid = idx < n_seg
    return RequestList(
        jnp.where(valid, seg_off, PAD_OFFSET),
        jnp.where(valid, seg_len, 0),
        n_seg.astype(jnp.int32),
    )


def aggregate(lists: RequestList) -> RequestList:
    """Full aggregator step: merge-sort per-sender lists, then coalesce."""
    return coalesce_sorted(merge_sorted(lists))


def coalesce_ratio(before: RequestList, after: RequestList) -> jax.Array:
    """Fraction of requests remaining after coalescing (lower = better)."""
    return after.count.astype(jnp.float32) / jnp.maximum(
        before.count.astype(jnp.float32), 1.0)


def pack_data(r: RequestList, starts: jax.Array, data: jax.Array,
              out_len: int, base: jax.Array | int = 0) -> jax.Array:
    """Scatter request payloads into a contiguous buffer.

    This is the "memory operation for moving the request data into a
    contiguous space based on the sorted offsets" (paper §V-A) and the
    aggregator-side placement into its file domain.

    r:      requests (element offsets into the *output* space).
    starts: int32[cap] — start of each request's payload within ``data``.
    data:   the concatenated payload elements for this sender set.
    out_len: length of the output buffer.
    base:   subtracted from offsets (e.g. the file-domain start).

    Elements mapping outside [0, out_len) are dropped — that is how a
    device ignores requests outside its file domain.
    """
    dcap = data.shape[0]
    # walk a contiguous "element stream": element e belongs to request
    # req_of[e] at index `within` inside that request. Its source lives at
    # starts[req] + within in `data` (slab gaps allowed); its destination
    # is offsets[req] + within - base in the output buffer.
    req_of = segment_ids(r.lengths, dcap)
    eidx = jnp.arange(dcap, dtype=jnp.int32)
    packed_starts = exclusive_prefix_sum(r.lengths)
    within = eidx - packed_starts[req_of]
    src = starts[req_of] + within
    dst = r.offsets[req_of] + within - base
    total = jnp.sum(r.lengths, dtype=jnp.int32)
    live = eidx < total
    vals = data[jnp.clip(src, 0, dcap - 1)]
    # positive OOB sentinel: .at[] wraps negative indices
    dst = jnp.where(live, dst, out_len)
    out = jnp.zeros((out_len,), dtype=data.dtype)
    return out.at[dst].set(vals, mode="drop")


def unpack_data(r: RequestList, starts: jax.Array, buf: jax.Array,
                out_len: int, base: jax.Array | int = 0) -> jax.Array:
    """Gather request payloads out of a contiguous buffer (read path)."""
    req_of = segment_ids(r.lengths, out_len)
    within = jnp.arange(out_len, dtype=jnp.int32) - starts[req_of]
    pos = r.offsets[req_of] + within - base
    total = jnp.sum(r.lengths, dtype=jnp.int32)
    pos = jnp.where(jnp.arange(out_len, dtype=jnp.int32) < total, pos, 0)
    vals = buf[jnp.clip(pos, 0, buf.shape[0] - 1)]
    return jnp.where(jnp.arange(out_len, dtype=jnp.int32) < total, vals, 0)


def request_starts(r: RequestList) -> jax.Array:
    """Start position of each request's payload in the packed data buffer."""
    return exclusive_prefix_sum(r.lengths)

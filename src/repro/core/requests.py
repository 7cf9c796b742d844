"""Request model for collective I/O.

An I/O request list is the JAX analogue of ROMIO's flattened MPI file
view: a list of (file offset, length) pairs, sorted in monotonically
nondecreasing offset order per rank (required by MPI_File_write_all and
relied upon by the paper's heap merge-sort).

XLA requires static shapes, so request lists are fixed-capacity arrays
with a ``count`` scalar; unused slots are padded with ``PAD_OFFSET``
(which sorts to the end) and zero length.

Units: offsets and lengths are in ELEMENTS (4-byte words), not bytes.
TPU Pallas has no native int64, so offsets are int32 — one "file" (a
serialized checkpoint byte-space) addresses up to 2^31 elements = 8 GiB.
Larger paper-scale patterns (up to 200 GiB) are handled by the analytical
cost model plus scaled empirical runs (see DESIGN.md §7).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

ELEM_BYTES = 4  # element = one 4-byte word
PAD_OFFSET = np.int32(2**31 - 1)


class RequestList(NamedTuple):
    """Fixed-capacity list of (offset, length) pairs, offset-sorted.

    offsets: int32[cap] — element offsets into the file; PAD_OFFSET pad.
    lengths: int32[cap] — element counts; 0 for padding slots.
    count:   int32 scalar — number of valid leading entries.
    """

    offsets: jax.Array
    lengths: jax.Array
    count: jax.Array

    @property
    def capacity(self) -> int:
        return self.offsets.shape[-1]

    def valid_mask(self) -> jax.Array:
        return jnp.arange(self.capacity, dtype=jnp.int32) < self.count

    def total_elems(self) -> jax.Array:
        return jnp.sum(self.lengths, dtype=jnp.int32)


# Scan primitives. A node's share of a real workload puts tens of millions
# of payload elements and requests through every round, and at those
# sizes the TPU compiler takes minutes over a single jnp.cumsum (one
# reduce-window as long as the array), an unhinted scatter, or a sort.
# The helpers below compute the same values from short reduce-windows and
# scatters whose indices are sorted, which compile in seconds.

SCAN_BLOCK = 1024


def prefix_sum(x: jax.Array) -> jax.Array:
    """Inclusive int32 prefix sum along the last axis — ``jnp.cumsum(x,
    axis=-1)`` computed as a blocked scan: a cumsum within each
    ``SCAN_BLOCK``-wide block plus the (recursively summed) block
    totals."""
    x = x.astype(jnp.int32)
    n = x.shape[-1]
    if n <= SCAN_BLOCK:
        return jnp.cumsum(x, axis=-1)
    lead = x.shape[:-1]
    pad = [(0, 0)] * len(lead) + [(0, -n % SCAN_BLOCK)]
    inner = jnp.cumsum(jnp.pad(x, pad).reshape(*lead, -1, SCAN_BLOCK),
                       axis=-1)
    last = inner[..., -1]
    out = inner + (prefix_sum(last) - last)[..., None]
    return out.reshape(*lead, -1)[..., :n]


def exclusive_prefix_sum(x: jax.Array) -> jax.Array:
    """``prefix_sum(x) - x``: the sum of the elements before each one."""
    return prefix_sum(x) - x.astype(jnp.int32)


def segment_ids(lengths: jax.Array, total: int) -> jax.Array:
    """Index of the request each of ``total`` packed payload elements
    belongs to: ``jnp.repeat(arange(cap), lengths,
    total_repeat_length=total)``, by the same scatter-and-scan, with the
    (nondecreasing) request starts declared sorted."""
    marks = jnp.zeros((total,), jnp.int32).at[
        exclusive_prefix_sum(lengths)].add(1, mode="drop",
                                           indices_are_sorted=True)
    return prefix_sum(marks) - 1


def compact(keep: jax.Array, *xs: jax.Array) -> tuple[jax.Array, ...]:
    """Stable compaction: each ``x[keep]`` moved to the front in order,
    zeros behind — ``x[argsort(~keep, stable=True)]`` on the kept
    prefix. Every element adds itself (or 0 when dropped) at its
    exclusive kept-rank, a nondecreasing index, so the scatter is
    sorted and each slot receives exactly one kept value."""
    dst = exclusive_prefix_sum(keep)
    return tuple(jnp.zeros_like(x).at[dst].add(
        jnp.where(keep, x, jnp.zeros((), x.dtype)), mode="drop",
        indices_are_sorted=True) for x in xs)


def make_requests(offsets, lengths, capacity: int | None = None) -> RequestList:
    """Build a RequestList from (possibly shorter) offset/length arrays."""
    offsets = jnp.asarray(offsets, dtype=jnp.int32)
    lengths = jnp.asarray(lengths, dtype=jnp.int32)
    n = offsets.shape[0]
    cap = capacity if capacity is not None else n
    if cap < n:
        raise ValueError(f"capacity {cap} < number of requests {n}")
    off = jnp.full((cap,), PAD_OFFSET, dtype=jnp.int32).at[:n].set(offsets)
    ln = jnp.zeros((cap,), dtype=jnp.int32).at[:n].set(lengths)
    return RequestList(off, ln, jnp.int32(n))


def empty_requests(capacity: int) -> RequestList:
    return RequestList(
        jnp.full((capacity,), PAD_OFFSET, dtype=jnp.int32),
        jnp.zeros((capacity,), dtype=jnp.int32),
        jnp.int32(0),
    )


def is_sorted(r: RequestList) -> jax.Array:
    """True if valid entries are in nondecreasing offset order."""
    off = jnp.where(r.valid_mask(), r.offsets, PAD_OFFSET)
    return jnp.all(off[:-1] <= off[1:])


def mask_invalid(r: RequestList) -> RequestList:
    """Force padding convention on all slots >= count."""
    m = r.valid_mask()
    return RequestList(
        jnp.where(m, r.offsets, PAD_OFFSET),
        jnp.where(m, r.lengths, 0),
        r.count,
    )


def split_at_stripes(r: RequestList, stripe_size: int, max_spans: int,
                     data_cap: int | None = None) -> RequestList:
    """Split every request at stripe boundaries.

    After splitting, each request lies entirely within one stripe, which
    is what lets a request be routed to exactly one global aggregator
    (ROMIO splits requests across file-domain boundaries the same way).
    Each input request may span at most ``max_spans`` stripes; output
    capacity is cap * max_spans. Given ``data_cap``, the most the
    lengths can sum to, the capacity is the tighter bound on the total
    span count below.
    """
    cap = r.capacity
    out_cap = cap * max_spans
    if data_cap is not None:
        # a request of length L >= 1 straddles at most ceil((L-1)/S)
        # boundaries, < (L-1)/S + 1; summed over the requests that is
        # fewer than data_cap/S + cap extra spans
        out_cap = cap + min(cap * (max_spans - 1),
                            cap + data_cap // stripe_size)
    o = r.offsets.astype(jnp.int32)
    l = r.lengths
    s0 = o // stripe_size
    # spans of request i: the stripes it touches, at most max_spans
    n = jnp.where(r.valid_mask() & (l > 0),
                  jnp.minimum((o + l - 1) // stripe_size - s0 + 1, max_spans),
                  0)
    # output slot k holds span k - first[i] of request i = req[k]: span j
    # covers [max(o, (s0+j)*S), min(o+l, (s0+j+1)*S)), in request order
    req = segment_ids(n, out_cap)
    j = jnp.arange(out_cap, dtype=jnp.int32) - exclusive_prefix_sum(n)[req]
    lo = jnp.maximum(o[req], (s0[req] + j) * stripe_size)
    hi = jnp.minimum(o[req] + l[req], (s0[req] + j + 1) * stripe_size)
    count = jnp.minimum(jnp.sum(n, dtype=jnp.int32), out_cap)
    live = jnp.arange(out_cap, dtype=jnp.int32) < count
    return RequestList(jnp.where(live, lo, PAD_OFFSET),
                       jnp.where(live, hi - lo, 0), count)


def to_numpy(r: RequestList) -> tuple[np.ndarray, np.ndarray]:
    """Return the valid (offsets, lengths) as host numpy arrays."""
    n = int(r.count)
    return np.asarray(r.offsets[:n]), np.asarray(r.lengths[:n])

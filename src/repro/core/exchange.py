"""Static-shape request/data routing primitives for SPMD collective I/O.

MPI two-phase I/O routes each request to the global aggregator owning its
file domain with point-to-point sends. Under SPMD every device runs the
same program with static shapes, so routing becomes: bucket requests (and
their payload elements) by destination into fixed-capacity per-destination
buckets, then exchange buckets with ``lax.all_to_all`` over a mesh axis.

Bucketing preserves offset order inside each bucket (stable grouping of an
offset-sorted input), which is what lets downstream aggregators merge-sort
cheaply and coalesce effectively.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.requests import (PAD_OFFSET, RequestList,
                                 exclusive_prefix_sum, mask_invalid,
                                 segment_ids)


class Buckets(NamedTuple):
    """Per-destination request buckets plus packed payload buckets.

    offsets: int32[n_dest, req_cap]
    lengths: int32[n_dest, req_cap]
    counts:  int32[n_dest]
    data:    dtype[n_dest, data_cap] — payload elements, packed in request
             order within each bucket (receiver recomputes starts from
             lengths).
    dropped_requests / dropped_elems: int32 scalars — overflow accounting
             (capacity misconfiguration is observable, never silent).
    """

    offsets: jax.Array
    lengths: jax.Array
    counts: jax.Array
    data: jax.Array
    dropped_requests: jax.Array
    dropped_elems: jax.Array


def sort_with(r: RequestList, *extras: jax.Array):
    """Sort requests by offset, permuting ``extras`` identically.

    Requires the padding-by-construction convention (invalid slots have
    offset PAD_OFFSET and length 0) but NOT the prefix convention —
    padding may be interspersed (e.g. flattened buckets); sorting
    compacts the valid entries to the front.
    """
    order = jnp.argsort(r.offsets, stable=True)
    sorted_r = RequestList(r.offsets[order], r.lengths[order], r.count)
    return (sorted_r, *[e[order] for e in extras])


def bucket_by_dest(r: RequestList, starts: jax.Array, data: jax.Array,
                   dest: jax.Array, n_dest: int, req_cap: int,
                   data_cap: int) -> Buckets:
    """Group requests + payload elements into per-destination buckets.

    r:      offset-sorted requests (element offsets in the file).
    starts: payload start of each request inside ``data``.
    dest:   int32[cap] destination id in [0, n_dest) per request.
    """
    cap = r.capacity
    in_dcap = data.shape[0]
    valid = r.valid_mask()
    d = jnp.where(valid, dest, n_dest).astype(jnp.int32)  # invalid -> sink

    # --- request-level grouping -------------------------------------
    order = jnp.argsort(d, stable=True)        # groups in offset order
    go, gl, gd = r.offsets[order], r.lengths[order], d[order]
    grp_counts = jax.ops.segment_sum(valid.astype(jnp.int32), d,
                                     num_segments=n_dest + 1)
    grp_start = exclusive_prefix_sum(grp_counts)
    pos = jnp.arange(cap, dtype=jnp.int32) - grp_start[gd]
    req_ok = (gd < n_dest) & (pos < req_cap)
    # NB: .at[] wraps negative indices (NumPy semantics); the drop
    # sentinel must be out-of-range POSITIVE.
    scatter_idx = jnp.where(req_ok, gd * req_cap + pos, n_dest * req_cap)
    out_off = jnp.full((n_dest * req_cap,), PAD_OFFSET, jnp.int32)
    out_off = out_off.at[scatter_idx].set(go, mode="drop")
    out_len = jnp.zeros((n_dest * req_cap,), jnp.int32)
    out_len = out_len.at[scatter_idx].set(gl, mode="drop")
    counts = jnp.minimum(grp_counts[:n_dest], req_cap)
    dropped_req = jnp.sum(jnp.maximum(grp_counts[:n_dest] - req_cap, 0))

    # --- element-level routing ---------------------------------------
    # payload start of each request within its destination bucket:
    # prefix of lengths among same-dest requests placed before it.
    gpre = exclusive_prefix_sum(gl)                 # global prefix, grouped
    elem_grp_start = exclusive_prefix_sum(
        jax.ops.segment_sum(jnp.where(valid, r.lengths, 0), d,
                            num_segments=n_dest + 1))
    dstart_grouped = gpre - elem_grp_start[gd]      # within-dest start
    req_dstart = jnp.zeros((cap,), jnp.int32).at[order].set(dstart_grouped)

    total = jnp.sum(jnp.where(valid, r.lengths, 0), dtype=jnp.int32)
    eidx = jnp.arange(in_dcap, dtype=jnp.int32)
    req_of = segment_ids(jnp.where(valid, r.lengths, 0), in_dcap)
    e_valid = eidx < total
    e_dest = d[req_of]
    e_pos = req_dstart[req_of] + (eidx - starts[req_of])
    e_ok = e_valid & (e_dest < n_dest) & (e_pos < data_cap) & (e_pos >= 0)
    e_scatter = jnp.where(e_ok, e_dest * data_cap + e_pos, n_dest * data_cap)
    out_data = jnp.zeros((n_dest * data_cap,), data.dtype)
    out_data = out_data.at[e_scatter].set(data, mode="drop")
    # payload past the end of ``data`` (total > in_dcap) is dropped too
    dropped_elems = (jnp.sum(e_valid & (e_dest < n_dest) & ~e_ok)
                     + jnp.maximum(total - in_dcap, 0))

    return Buckets(out_off.reshape(n_dest, req_cap),
                   out_len.reshape(n_dest, req_cap),
                   counts, out_data.reshape(n_dest, data_cap),
                   dropped_req.astype(jnp.int32),
                   dropped_elems.astype(jnp.int32))


def flatten_buckets(offsets: jax.Array, lengths: jax.Array,
                    counts: jax.Array, data: jax.Array):
    """Merge a stack of buckets [..., B, cap] into one flat request list
    with payload starts pointing into the flattened data buffer.
    """
    b_off = offsets.reshape(-1, offsets.shape[-1])
    b_len = lengths.reshape(-1, lengths.shape[-1])
    nb, cap = b_off.shape
    dcap = data.shape[-1]
    # starts within each bucket, offset by the bucket's slab in flat data
    per_bucket_starts = exclusive_prefix_sum(b_len)
    slab = (jnp.arange(nb, dtype=jnp.int32) * dcap)[:, None]
    starts = (per_bucket_starts + slab).reshape(-1)
    # NOTE: padding is interspersed (per-bucket suffixes) — the prefix
    # convention does not hold until the list is sorted. Invalid slots
    # are self-describing (PAD_OFFSET / length 0) by bucket construction.
    r = RequestList(b_off.reshape(-1), b_len.reshape(-1),
                    jnp.sum(counts, dtype=jnp.int32))
    return r, starts, data.reshape(-1)


def repack_sorted(r_sorted: RequestList, starts: jax.Array,
                  data_flat: jax.Array, out_cap: int) -> jax.Array:
    """Pack payloads contiguously in sorted-request order.

    After this, the payload of any coalesced run of contiguous requests
    occupies one contiguous span — which is exactly why TAM's local
    aggregators can forward coalesced metadata with repacked data.
    """
    total = jnp.sum(r_sorted.lengths, dtype=jnp.int32)
    eidx = jnp.arange(out_cap, dtype=jnp.int32)
    req_of = segment_ids(r_sorted.lengths, out_cap)
    new_starts = exclusive_prefix_sum(r_sorted.lengths)
    src = starts[req_of] + (eidx - new_starts[req_of])
    vals = data_flat[jnp.clip(src, 0, data_flat.shape[0] - 1)]
    return jnp.where(eidx < total, vals, jnp.zeros((), data_flat.dtype))

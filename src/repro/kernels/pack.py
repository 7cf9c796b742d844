"""Pallas TPU kernel: pack request payloads into a contiguous buffer.

The third intra-node aggregation component in the paper: "memory
operation for moving the request data into a contiguous space based on
the sorted offsets" (SV-A), and the aggregator-side placement of payload
into the file domain.

GPU/CPU implementations scatter (out[dst[e]] = data[e]); TPUs hate
scatters. We invert it into a GATHER over output tiles: each grid step
produces one aligned output tile; for every output position p it binary-
searches the (VMEM-resident) sorted offset array for the covering
request r — offsets[r] <= p + base < offsets[r] + lengths[r] — and pulls
data[starts[r] + (p + base - offsets[r])], else 0. log2(cap) select
steps, fully vectorized over the tile; the request metadata block stays
pinned in VMEM across tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode

MAX_REQ_BLOCK = 32768
TILE = 4096


def _searchsorted_right(sorted_keys: jax.Array, queries: jax.Array) -> jax.Array:
    """Vectorized binary search: index of last key <= query (-1 if none)."""
    n = sorted_keys.shape[0]
    lo = jnp.full(queries.shape, -1, jnp.int32)
    hi = jnp.full(queries.shape, n, jnp.int32)
    steps = max(n.bit_length(), 1)
    for _ in range(steps):
        mid = (lo + hi) // 2
        mid_c = jnp.clip(mid, 0, n - 1)
        take = sorted_keys[mid_c] <= queries
        lo = jnp.where((hi - lo > 1) & take, mid, lo)
        hi = jnp.where((hi - lo > 1) & ~take, mid, hi)
    return lo


def _pack_tile(off, ln, starts, data, base, tile_start, tile):
    p = (jax.lax.broadcasted_iota(jnp.int32, (tile, 1), 0).reshape(tile)
         + tile_start + base)
    r = _searchsorted_right(off, p)
    r_c = jnp.clip(r, 0, off.shape[0] - 1)
    within = p - off[r_c]
    covered = (r >= 0) & (within < ln[r_c])
    src = jnp.clip(starts[r_c] + within, 0, data.shape[0] - 1)
    return jnp.where(covered, data[src], jnp.zeros((), data.dtype))


@functools.partial(jax.jit, static_argnames=("out_len", "interpret"))
def pack(offsets: jax.Array, lengths: jax.Array, starts: jax.Array,
         data: jax.Array, base, out_len: int, *, interpret: bool | None = None):
    """Gather-style pack of payloads into a dense [out_len] buffer.

    offsets/lengths/starts: int32[cap] — offset-SORTED, non-overlapping
    requests (padding at tail). starts[i] locates request i's payload in
    ``data``. base: int32 scalar — file-domain start. Output positions
    not covered by any request are 0.
    """
    cap = offsets.shape[0]
    if cap > MAX_REQ_BLOCK:
        raise ValueError(f"request block {cap} > {MAX_REQ_BLOCK}")
    if out_len % TILE:
        raise ValueError(f"out_len must be a multiple of {TILE}")
    n_tiles = out_len // TILE
    base = jnp.asarray(base, jnp.int32).reshape(1)

    meta = pl.BlockSpec((cap,), lambda i: (0,))
    dspec = pl.BlockSpec(data.shape, lambda i: (0,))
    bspec = pl.BlockSpec((1,), lambda i: (0,))
    out_spec = pl.BlockSpec((TILE,), lambda i: (i,))

    def kernel(o, l, s, d, b, out):
        tile_start = pl.program_id(0) * TILE
        out[...] = _pack_tile(o[...], l[...], s[...], d[...], b[0],
                              tile_start, TILE)

    return pl.pallas_call(
        kernel,
        grid=(n_tiles,),
        in_specs=[meta, meta, meta, dspec, bspec],
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((out_len,), data.dtype),
        interpret=interpret_mode(interpret),
    )(offsets, lengths, starts, data, base)

"""Pallas TPU kernel: the fused per-round drain hot path.

Each round, every aggregator drains one cb window: sort the merged
request list by offset, then pack the window payload AND the coverage
mask into the domain buffer. Unfused, that is three kernel launches —
``kernels/sort.py`` (bitonic), then ``kernels/pack.py`` twice (window
payload + mask) — i.e. three HBM round-trips of the request metadata
per round, plus a second binary-search sweep the mask pack repeats
verbatim. At the ranks-per-node the source paper targets (SIV-D: the
aggregator-side sort dominates the communication phase), the metadata
traffic is the hot path.

``fused_sort_pack`` does all of it in ONE ``pallas_call``:

* grid step 0 runs the bitonic network (``kernels.sort``'s compare-
  exchange body, VMEM-resident) and parks the sorted metadata in VMEM
  scratch — TPU grids are sequential, so the scratch persists;
* every grid step then produces one aligned output tile of BOTH the
  window and the mask from a SINGLE binary search per position
  (``kernels.pack``'s gather formulation) — the mask is a byproduct of
  the coverage test the payload gather already performs, so the second
  search sweep of the unfused path disappears entirely.

``zero_skip_encode`` is the codec half of the fusion: the rle codec's
SPMD lowering is a zero-skipping compaction ``(values, positions)``
(``core.codec.RleCodec.jax_encode`` — a stable argsort on zero-ness).
Here it is one VMEM block per destination bucket: a Hillis-Steele
prefix sum ranks the nonzeros in position order and a single in-block
scatter compacts them — byte-identical to the argsort form (asserted
by the rounds_checks fuzz), without materializing the argsort
permutation through HBM.

``zero_skip_decode`` is the read-direction half (the PR 6 "fuse merge
+ codec decode" leftover): the rle ``jax_decode`` expands the
compacted ``(values, positions)`` wire form back into the window by a
row-wise scatter through an HBM-materialized (rows, cap+1) staging
buffer. Here the scatter runs in VMEM, one block per gathered window —
the merge of the fetched window into the reader's shard consumes the
kernel's output directly, so the staging buffer never touches HBM.
Byte-identical to ``jax_decode`` (rounds_checks read fuzz).

Both kernels are selected by the planner's ``lower_kernels`` pass
(``IOPlan.kernel_fusion == "fused_round"``) and consumed by
``core.rounds`` — byte-identity with the unfused jnp path under every
placement x codec x depth is the acceptance contract (rounds_checks).
Off the TPU they run in interpret mode (``kernels.interpret_mode``); blocks
aim at the TPU constraints (power-of-two request blocks, aligned output
tiles, >= 2D iota via broadcasted_iota).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode
from repro.kernels.pack import MAX_REQ_BLOCK, TILE, _searchsorted_right
from repro.kernels.sort import _bitonic_sort_body


@functools.partial(jax.jit, static_argnames=("out_len", "interpret"))
def fused_sort_pack(offsets: jax.Array, lengths: jax.Array,
                    starts: jax.Array, data: jax.Array, base,
                    out_len: int, *, interpret: bool | None = None):
    """Sort + dual-pack one drain window in a single kernel.

    offsets/lengths/starts: int32[cap] request metadata, cap a power of
    two <= MAX_REQ_BLOCK, padding at PAD_OFFSET/0 (UNSORTED — the sort
    happens inside). data: the flat payload buffer starts[] points
    into. base: int32 scalar, the window's domain offset. Returns
    ``(window, mask)``, both [out_len]: the packed payload and its
    coverage mask (1 where any request covers the position, else 0),
    in ``data.dtype`` — exactly what the two unfused ``pack_data``
    calls of the drain produce.
    """
    cap = offsets.shape[0]
    if cap & (cap - 1) or cap > MAX_REQ_BLOCK:
        raise ValueError(
            f"request block {cap} must be a power of two <= {MAX_REQ_BLOCK}")
    if out_len % TILE:
        raise ValueError(f"out_len must be a multiple of {TILE}")
    n_tiles = out_len // TILE
    base = jnp.asarray(base, jnp.int32).reshape(1)

    meta = pl.BlockSpec((cap,), lambda i: (0,))
    dspec = pl.BlockSpec(data.shape, lambda i: (0,))
    bspec = pl.BlockSpec((1,), lambda i: (0,))
    tspec = pl.BlockSpec((TILE,), lambda i: (i,))

    def kernel(o, l, s, d, b, win, mask, so, sl, ss):
        # the sort runs once; the sorted metadata rides VMEM scratch
        # across the (sequential) output tiles
        @pl.when(pl.program_id(0) == 0)
        def _sort():
            key, (ln, st) = _bitonic_sort_body(o[...], (l[...], s[...]))
            so[...] = key
            sl[...] = ln
            ss[...] = st

        tile_start = pl.program_id(0) * TILE
        p = (jax.lax.broadcasted_iota(jnp.int32, (TILE, 1), 0)
             .reshape(TILE) + tile_start + b[0])
        off, ln, st = so[...], sl[...], ss[...]
        r = _searchsorted_right(off, p)          # ONE search, two packs
        r_c = jnp.clip(r, 0, cap - 1)
        within = p - off[r_c]
        covered = (r >= 0) & (within < ln[r_c])
        dd = d[...]
        src = jnp.clip(st[r_c] + within, 0, dd.shape[0] - 1)
        zero = jnp.zeros((), dd.dtype)
        win[...] = jnp.where(covered, dd[src], zero)
        mask[...] = jnp.where(covered, jnp.ones((), dd.dtype), zero)

    win, mask = pl.pallas_call(
        kernel,
        grid=(n_tiles,),
        in_specs=[meta, meta, meta, dspec, bspec],
        out_specs=[tspec, tspec],
        out_shape=[jax.ShapeDtypeStruct((out_len,), data.dtype)] * 2,
        scratch_shapes=[pltpu.VMEM((cap,), jnp.int32)] * 3,
        interpret=interpret_mode(interpret),
    )(offsets, lengths, starts, data, base)
    return win, mask


@functools.partial(jax.jit, static_argnames=("interpret",))
def zero_skip_encode(data: jax.Array, *, interpret: bool | None = None):
    """Zero-skipping compaction of payload rows — the rle codec's SPMD
    wire form, fused into one VMEM block per row.

    data: [rows, n] with n a power of two. Returns ``(vals, pos)``:
    nonzero values compacted to the front in position order, their
    original positions alongside (-1 in the padding) — byte-identical
    to ``RleCodec.jax_encode``'s stable-argsort formulation.
    """
    rows, n = data.shape
    if n & (n - 1):
        raise ValueError(f"row length {n} must be a power of two")
    block = pl.BlockSpec((1, n), lambda i: (i, 0))

    def kernel(d, vals, pos):
        v = d[0, :]
        nz = (v != 0).astype(jnp.int32)
        # inclusive Hillis-Steele prefix sum -> exclusive rank
        run = nz
        shift = 1
        while shift < n:
            shifted = jnp.pad(run, (shift, 0))[:n]
            run = run + shifted
            shift *= 2
        rank = run - nz
        idx = jnp.where(nz == 1, rank, n)        # zeros -> drop sentinel
        i = jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0).reshape(n)
        vals[0, :] = jnp.zeros((n,), v.dtype).at[idx].set(v, mode="drop")
        pos[0, :] = jnp.full((n,), -1, jnp.int32).at[idx].set(
            i, mode="drop")

    return pl.pallas_call(
        kernel,
        grid=(rows,),
        in_specs=[block],
        out_specs=[block, block],
        out_shape=[jax.ShapeDtypeStruct((rows, n), data.dtype),
                   jax.ShapeDtypeStruct((rows, n), jnp.int32)],
        interpret=interpret_mode(interpret),
    )(data)


@functools.partial(jax.jit, static_argnames=("interpret",))
def zero_skip_decode(vals: jax.Array, pos: jax.Array, *,
                     interpret: bool | None = None):
    """Expand zero-skip compacted rows back into dense windows — the
    rle codec's decode scatter, fused into one VMEM block per row.

    vals/pos: [rows, n] with n a power of two, ``pos == -1`` in the
    padding (``zero_skip_encode``'s wire layout). Returns [rows, n] in
    ``vals.dtype``, zeros where no position lands — byte-identical to
    ``RleCodec.jax_decode``'s staged-scatter formulation, minus its
    HBM (rows, cap+1) staging buffer.
    """
    rows, n = vals.shape
    if n & (n - 1):
        raise ValueError(f"row length {n} must be a power of two")
    if pos.shape != vals.shape:
        raise ValueError(f"vals {vals.shape} / pos {pos.shape} mismatch")
    block = pl.BlockSpec((1, n), lambda i: (i, 0))

    def kernel(v, p, out):
        vv = v[0, :]
        pp = p[0, :]
        idx = jnp.where(pp >= 0, pp, n)          # padding -> drop sentinel
        out[0, :] = jnp.zeros((n,), vv.dtype).at[idx].set(vv, mode="drop")

    return pl.pallas_call(
        kernel,
        grid=(rows,),
        in_specs=[block, block],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct((rows, n), vals.dtype),
        interpret=interpret_mode(interpret),
    )(vals, pos)

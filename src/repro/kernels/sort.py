"""Pallas TPU bitonic sort of offset-length request lists.

The paper's aggregators spend ``O((P*k/P_L) log(P/P_L))`` in a heap
merge-sort of offset-length pairs — the dominant compute hot spot of the
communication phase at scale (SIV-D). A pointer-chasing heap is the wrong
shape for a TPU; the VPU wants a data-parallel network. We therefore sort
with a **bitonic network held entirely in VMEM**: log2(n)*(log2(n)+1)/2
vectorized compare-exchange sweeps, each a full-lane min/max plus masked
select — no scalar control flow, MXU-free, bandwidth-bound on VMEM only.

Hardware adaptation notes (DESIGN.md S7.6):
* one block sorts up to ``MAX_BLOCK`` pairs in VMEM. 32768 pairs x
  (key + 2 carries) x 4 B = 384 KiB << 16 MiB VMEM, leaving room for the
  double-buffered pipeline. Per-round request counts beyond MAX_BLOCK are
  handled by the ops.py wrapper (chunk sort + jnp merge), mirroring
  ROMIO's multi-round bounding of per-round work.
* compare-exchange partners at distance j are materialized with a
  reshape to (n/2j, 2, j) and a middle-axis flip, so every step is a
  contiguous vector op rather than a gather.
* padding (PAD_OFFSET) sorts to the end naturally; ties keep both
  elements' own carries, so the sort is safe for duplicated keys.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode

MAX_BLOCK = 32768  # pairs per VMEM block (power of two)


def _cmp_exchange(key: jax.Array, carries: tuple[jax.Array, ...],
                  j: int, k: int):
    """One bitonic compare-exchange sweep at distance j, block size k."""
    n = key.shape[0]
    i = jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0).reshape(n)
    partner_view = lambda x: x.reshape(-1, 2, j)[:, ::-1, :].reshape(n)
    pkey = partner_view(key)
    take_min = ((i & j) == 0) == ((i & k) == 0)
    new_key = jnp.where(take_min, jnp.minimum(key, pkey),
                        jnp.maximum(key, pkey))
    took_partner = jnp.where(take_min, pkey < key, pkey > key)
    new_carries = tuple(
        jnp.where(took_partner, partner_view(c), c) for c in carries)
    return new_key, new_carries


def _bitonic_sort_body(key, carries):
    n = key.shape[0]
    k = 2
    while k <= n:
        j = k // 2
        while j >= 1:
            key, carries = _cmp_exchange(key, carries, j, k)
            j //= 2
        k *= 2
    return key, carries


def _sort_kernel(off_ref, len_ref, carry_ref, off_out, len_out, carry_out):
    key = off_ref[...]
    carries = (len_ref[...], carry_ref[...])
    key, carries = _bitonic_sort_body(key, carries)
    off_out[...] = key
    len_out[...] = carries[0]
    carry_out[...] = carries[1]


@functools.partial(jax.jit, static_argnames=("interpret",))
def bitonic_sort(offsets: jax.Array, lengths: jax.Array, carry: jax.Array,
                 *, interpret: bool | None = None):
    """Sort one batch of request blocks by offset.

    offsets/lengths/carry: int32[b, n] with n a power of two <= MAX_BLOCK.
    Returns the three arrays sorted along the last axis by offset.
    The grid iterates over b — each grid step sorts one block in VMEM.
    """
    b, n = offsets.shape
    if n & (n - 1) or n > MAX_BLOCK:
        raise ValueError(f"block length {n} must be a power of two <= {MAX_BLOCK}")
    block = pl.BlockSpec((1, n), lambda i: (i, 0))
    flat = pl.BlockSpec((1, n), lambda i: (i, 0))

    def kernel(o, l, c, oo, lo, co):
        key = o[0, :]
        carries = (l[0, :], c[0, :])
        key, carries = _bitonic_sort_body(key, carries)
        oo[0, :] = key
        lo[0, :] = carries[0]
        co[0, :] = carries[1]

    out_shape = [jax.ShapeDtypeStruct((b, n), jnp.int32)] * 3
    return pl.pallas_call(
        kernel,
        grid=(b,),
        in_specs=[block, block, block],
        out_specs=[flat, flat, flat],
        out_shape=out_shape,
        interpret=interpret_mode(interpret),
    )(offsets, lengths, carry)

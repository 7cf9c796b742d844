"""Jit'd public wrappers around the Pallas kernels.

Handles: padding to power-of-two block sizes, RequestList integration,
and large-list chunking (chunk-sort + merge). ``interpret=None`` passes
through to the kernels, which resolve it from the backend
(``kernels.interpret_mode``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.requests import PAD_OFFSET, RequestList
from repro.kernels import coalesce_kernel, pack as pack_mod, sort as sort_mod


def _next_pow2(n: int) -> int:
    return 1 << max(n - 1, 1).bit_length()


def _pad_block(x: jax.Array, n: int, fill) -> jax.Array:
    pad = n - x.shape[-1]
    if pad == 0:
        return x
    widths = [(0, 0)] * (x.ndim - 1) + [(0, pad)]
    return jnp.pad(x, widths, constant_values=fill)


def sort_requests_with(r: RequestList, starts: jax.Array,
                       interpret: bool | None = None):
    """Kernel-backed equivalent of ``exchange.sort_with(r, starts)``.

    Lists longer than one VMEM block are chunk-sorted by the kernel and
    k-way merged with a final jnp argsort of block-sorted runs (the merge
    is cheap relative to the in-block network; on TPU it would be a
    bitonic inter-block merge, see kernels/sort.py docstring).
    """
    cap = r.capacity
    n = _next_pow2(cap)
    if n <= sort_mod.MAX_BLOCK:
        off = _pad_block(r.offsets[None], n, PAD_OFFSET)
        ln = _pad_block(r.lengths[None], n, 0)
        st = _pad_block(starts[None], n, 0)
        so, sl, ss = sort_mod.bitonic_sort(off, ln, st, interpret=interpret)
        return (RequestList(so[0, :cap], sl[0, :cap], r.count), ss[0, :cap])
    # chunked path: sort blocks with the kernel, merge with argsort
    nb = -(-cap // sort_mod.MAX_BLOCK)
    padded = nb * sort_mod.MAX_BLOCK
    off = _pad_block(r.offsets, padded, PAD_OFFSET).reshape(nb, -1)
    ln = _pad_block(r.lengths, padded, 0).reshape(nb, -1)
    st = _pad_block(starts, padded, 0).reshape(nb, -1)
    so, sl, ss = sort_mod.bitonic_sort(off, ln, st, interpret=interpret)
    flat_o, flat_l, flat_s = so.reshape(-1), sl.reshape(-1), ss.reshape(-1)
    order = jnp.argsort(flat_o, stable=True)
    return (RequestList(flat_o[order][:cap], flat_l[order][:cap], r.count),
            flat_s[order][:cap])


def coalesce(r: RequestList, interpret: bool | None = None) -> RequestList:
    """Kernel-backed equivalent of ``coalesce.coalesce_sorted``."""
    cap = r.capacity
    n = min(_next_pow2(cap), max(_next_pow2(cap), 8))
    off = _pad_block(r.offsets[None], n, PAD_OFFSET)
    ln = _pad_block(r.lengths[None], n, 0)
    co, cl, cnt = coalesce_kernel.coalesce(off, ln, interpret=interpret)
    return RequestList(co[0, :cap], cl[0, :cap], cnt[0])


def pack(r: RequestList, starts: jax.Array, data: jax.Array, base,
         out_len: int, interpret: bool | None = None) -> jax.Array:
    """Kernel-backed equivalent of ``coalesce.pack_data``.

    Requires offset-sorted, non-overlapping requests (the condition the
    gather formulation exploits). out_len is padded to the tile size
    internally; the caller receives exactly [out_len].
    """
    cap = _next_pow2(r.capacity)
    off = _pad_block(r.offsets, cap, PAD_OFFSET)
    ln = _pad_block(r.lengths, cap, 0)
    st = _pad_block(starts, cap, 0)
    padded_out = -(-out_len // pack_mod.TILE) * pack_mod.TILE
    out = pack_mod.pack(off, ln, st, data, base, padded_out,
                        interpret=interpret)
    return out[:out_len]


def fused_drain_pack(r: RequestList, starts: jax.Array, data: jax.Array,
                     base, out_len: int, interpret: bool | None = None):
    """Kernel-backed equivalent of the drain's ``sort_with`` + two
    ``pack_data`` calls, in one ``pallas_call``
    (``kernels.fused_round.fused_sort_pack``).

    Takes the UNSORTED merged request list (the fusion absorbs the
    sort); returns ``(window, mask)``, both [out_len] in data.dtype.
    Selected by ``IOPlan.kernel_fusion == "fused_round"``.
    """
    from repro.kernels import fused_round

    cap = _next_pow2(r.capacity)
    off = _pad_block(r.offsets, cap, PAD_OFFSET)
    ln = _pad_block(r.lengths, cap, 0)
    st = _pad_block(starts, cap, 0)
    padded_out = -(-out_len // pack_mod.TILE) * pack_mod.TILE
    win, mask = fused_round.fused_sort_pack(off, ln, st, data, base,
                                            padded_out,
                                            interpret=interpret)
    return win[:out_len], mask[:out_len]


def rle_zero_skip_encode(data: jax.Array, interpret: bool | None = None):
    """Kernel-backed equivalent of ``RleCodec.jax_encode``'s zero-skip
    compaction (``kernels.fused_round.zero_skip_encode``): pads rows to
    a power of two, compacts, slices back. Returns ``(vals, pos)`` with
    the codec's exact wire layout (pos == -1 in the padding)."""
    from repro.kernels import fused_round

    lead, cap = data.shape[:-1], data.shape[-1]
    n = _next_pow2(cap)
    rows = data.reshape(-1, cap)
    padded = _pad_block(rows, n, 0)
    vals, pos = fused_round.zero_skip_encode(padded, interpret=interpret)
    return (vals[:, :cap].reshape(*lead, cap),
            pos[:, :cap].reshape(*lead, cap))


def rle_zero_skip_decode(parts, interpret: bool | None = None):
    """Kernel-backed equivalent of ``RleCodec.jax_decode``
    (``kernels.fused_round.zero_skip_decode``): pads the compacted
    ``(vals, pos)`` rows to a power of two (pos padding = -1, the drop
    sentinel), scatters in VMEM, slices back to the window shape."""
    from repro.kernels import fused_round

    vals, pos = parts
    lead, cap = vals.shape[:-1], vals.shape[-1]
    n = _next_pow2(cap)
    v = _pad_block(vals.reshape(-1, cap), n, 0)
    p = _pad_block(pos.reshape(-1, cap), n, -1)
    out = fused_round.zero_skip_decode(v, p, interpret=interpret)
    return out[:, :cap].reshape(*lead, cap)

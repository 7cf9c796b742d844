"""Two-layer (TAM-style) collectives for training-time communication.

Beyond-paper: the paper's congestion argument — aggregate inside the fast
domain first so the slow domain sees fewer endpoints and less metadata —
applied to gradient synchronization and MoE dispatch on a multi-pod mesh:

* ``two_layer_psum``    — reduce-scatter over the fast axis, all-reduce
  over the slow axis on the 1/q-size shard only, all-gather back over the
  fast axis. Slow-axis bytes drop from |g| to |g|/q per device.
* ``compressed_psum``   — same schedule with error-feedback int8 (or
  top-k) compression applied ONLY to the slow hop, the direct analogue of
  coalescing before the inter-node phase.
* ``two_layer_all_to_all`` — hierarchical MoE dispatch: tokens are
  exchanged within the pod first, combined per destination pod, then one
  aggregated inter-pod exchange.

These run inside ``shard_map`` bodies (they use axis names).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import codec as codec_mod


def _pad_to(x: jax.Array, mult: int) -> tuple[jax.Array, int]:
    n = x.shape[0]
    pad = (-n) % mult
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad,) + x.shape[1:], x.dtype)])
    return x, n


def two_layer_psum(x: jax.Array, fast_axis: str, slow_axis: str) -> jax.Array:
    """psum(x) over (fast, slow) with the TAM schedule.

    Mathematically identical to ``lax.psum(x, (fast, slow))``; the
    explicit schedule pins the slow-axis transfer to the scattered shard
    (1/q of the bytes) and exposes the slow hop for compression.
    """
    orig_shape = x.shape
    q = lax.axis_size(fast_axis)
    flat, n = _pad_to(x.reshape(-1), q)
    shard = lax.psum_scatter(flat, fast_axis, scatter_dimension=0,
                             tiled=True)                   # intra: RS
    shard = lax.psum(shard, slow_axis)                     # inter: AR (1/q)
    full = lax.all_gather(shard, fast_axis, axis=0, tiled=True)  # intra: AG
    return full[:n].reshape(orig_shape)


class ErrorFeedbackState:
    """Per-leaf residual for error-feedback compression (EF-SGD style)."""

    @staticmethod
    def init(x: jax.Array) -> jax.Array:
        return jnp.zeros_like(x)


# The int8 arithmetic moved to the shared slow-hop codec subsystem
# (``core.codec``, the "ef-int8" registry entry) so the collective-I/O
# round engine and this module compress the slow hop the same way; the
# old private names stay as aliases for callers that reached in.
def _int8_encode(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    return codec_mod.int8_encode(x)


def _int8_decode(q: jax.Array, scale: jax.Array) -> jax.Array:
    return codec_mod.int8_decode(q, scale)


def compressed_psum(x: jax.Array, residual: jax.Array, fast_axis: str,
                    slow_axis: str) -> tuple[jax.Array, jax.Array]:
    """Two-layer psum with error-feedback int8 on the slow hop only.

    The fast-axis reduce-scatter runs at full precision; the slow-axis
    all-reduce moves int8 (4x fewer slow-axis bytes on top of the 1/q
    from the schedule). The quantization error is fed back into
    ``residual`` and reapplied next step, preserving convergence
    (Karimireddy et al., 2019). Returns (psum_result, new_residual).

    Consumes the registry's ``ef-int8`` codec — the same encode/decode
    (and the same residual-riding contract) the round engine applies to
    the collective-I/O slow hop (``IOPlan.slow_hop_codec``).
    """
    ef = codec_mod.get_codec("ef-int8")
    orig_shape = x.shape
    q = lax.axis_size(fast_axis)
    flat, n = _pad_to(x.reshape(-1), q)
    shard = lax.psum_scatter(flat, fast_axis, scatter_dimension=0,
                             tiled=True)
    res_flat, _ = _pad_to(residual.reshape(-1), q)
    res_shard = lax.dynamic_slice_in_dim(
        res_flat, lax.axis_index(fast_axis) * shard.shape[0], shard.shape[0])
    wire, new_res_shard = ef.jax_encode(shard, res_shard)
    decoded = ef.jax_decode(wire)
    reduced = lax.psum(decoded, slow_axis)
    full = lax.all_gather(shard * 0 + reduced, fast_axis, axis=0, tiled=True)
    new_res = lax.all_gather(new_res_shard, fast_axis, axis=0, tiled=True)
    return (full[:n].reshape(orig_shape),
            new_res[:n].reshape(residual.shape))


def two_layer_all_to_all(x: jax.Array, fast_axis: str, slow_axis: str) -> jax.Array:
    """Hierarchical all-to-all over the flattened (slow, fast) rank space.

    x: [n_slow * n_fast, ...] — chunk d goes to global rank d. Executed as
    an intra-pod exchange that groups chunks by destination pod, then one
    inter-pod exchange of pod-aggregated slabs, then a final intra-pod
    redistribution. Equivalent permutation to a flat all_to_all over both
    axes, but every slow-axis message is a q-chunk aggregate (fewer,
    larger slow-axis transfers — TAM's congestion fix for MoE dispatch).
    """
    ns, nf = lax.axis_size(slow_axis), lax.axis_size(fast_axis)
    assert x.shape[0] == ns * nf, "leading dim must be n_slow * n_fast"
    tail = x.shape[1:]
    # group by (dest pod, dest fast slot): grouped[t, u] -> rank (t, u)
    grouped = x.reshape(ns, nf, *tail)
    # intra-pod: deliver every chunk to its destination FAST SLOT within
    # my pod. After this, device (s, f) holds intra[u', t] = the chunk
    # from fast peer u' destined to (pod t, slot f) — i.e. all chunks
    # that must leave pod s toward slot f, pre-gathered on one device.
    intra = lax.all_to_all(grouped, fast_axis, split_axis=1, concat_axis=0,
                           tiled=False).reshape(nf, ns, *tail)
    # inter-pod: ONE aggregated slow-axis exchange per device moves each
    # pod-slab to its destination pod; chunks are already at the right
    # fast slot, so this completes the permutation. inter[s', u'] = chunk
    # from global rank (s', u') destined to me.
    inter = lax.all_to_all(intra, slow_axis, split_axis=1, concat_axis=0,
                           tiled=False).reshape(ns, nf, *tail)
    return inter.reshape(ns * nf, *tail)


def tree_two_layer_psum(tree, fast_axis: str, slow_axis: str):
    return jax.tree.map(lambda g: two_layer_psum(g, fast_axis, slow_axis),
                        tree)


def tree_compressed_psum(tree, residuals, fast_axis: str, slow_axis: str):
    flat, treedef = jax.tree.flatten(tree)
    rflat = jax.tree.leaves(residuals)
    out, new_res = [], []
    for g, r in zip(flat, rflat):
        o, nr = compressed_psum(g, r, fast_axis, slow_axis)
        out.append(o)
        new_res.append(nr)
    return jax.tree.unflatten(treedef, out), jax.tree.unflatten(treedef, new_res)

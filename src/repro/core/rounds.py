"""Round-scheduled bounded-buffer exchange engine (DESIGN).

Why rounds
----------
ROMIO's Lustre driver (paper §II) never materializes a whole file
domain worth of incoming traffic at an aggregator: the two-phase
exchange runs in ROUNDS, each bounded by the aggregator's collective
buffer (``cb_buffer_size``, romio_cb_buffer_size). Our analytical model
already charges for this (``cost_model`` refinement 1: each round
re-runs the request exchange and re-pays the incast latency), but the
single-shot SPMD paths in ``twophase``/``tam`` exchanged everything at
once, so aggregator-side receive buffers grew as
``O(P * data_cap)`` — the per-rank payload capacity times every
participating rank. That caps the file size a fixed mesh can drive.

The protocol
------------
Aggregator ``g`` owns the contiguous file domain
``[g * domain_len, (g+1) * domain_len)``. :class:`RoundScheduler`
partitions every domain into ``domain_len / cb_buffer_size``
stripe-aligned windows; round ``t`` moves exactly the requests whose
offsets fall in window ``t`` of their destination domain:

1. **split** — requests are split at window boundaries once, up front
   (``requests.split_at_stripes``), so each request lives in exactly one
   (destination, round) window;
2. **select** — per round, the active requests are compacted to the
   front of a static-capacity list (offset order preserved);
3. **exchange** — the existing ``bucket_by_dest`` / ``all_to_all`` /
   ``flatten_buckets`` / ``sort_with`` pipeline runs with per-bucket
   payload capacity ``min(data_cap, cb_buffer_size)``;
4. **pack + merge** — each rank packs its received slice into a
   ``cb_buffer_size`` window image and the images are merged across the
   node's other receive streams with a masked max-combine
   (``lax.pmax``), NOT a gather: the merge buffer stays
   ``O(cb_buffer_size)`` instead of ``O(ranks_per_node * data_cap)``;
5. **accumulate** — the window is written into the carried domain
   buffer at ``t * cb_buffer_size`` and the loop (``lax.fori_loop``, so
   compiled size is round-count independent) advances.

Peak aggregator-side buffering is therefore
``n_nodes * min(data_cap, cb) + cb`` elements — independent of the
number of participating ranks (see
:func:`peak_aggregator_buffer_elems`, asserted by tests/test_rounds.py).
The same mesh can drive arbitrarily large files by holding
``cb_buffer_size`` fixed while rounds grow.

The depth-k pipeline ring (``depth`` / ``pipeline=True``)
---------------------------------------------------------
The serial loop pays ``exchange + drain`` per round. The pipelined loop
is a software pipeline over a RING of ``depth`` in-flight window
buffers (``depth=2`` is the classic double buffer; the ``pipeline``
bool remains as sugar for depth 2):

* **prologue** — rounds ``0..depth-2`` are exchanged into the ring
  (statically unrolled); nothing drains.
* **steady state** — iteration ``t`` (depth-1..n_rounds-1) exchanges
  round ``t`` into the freed buffer while DRAINING the OLDEST carried
  window, round ``t-(depth-1)`` (flatten → sort → pack → masked pmax
  merge → accumulate). The two halves share no data, so XLA is free to
  run the slow-axis ``all_to_all`` concurrently with the local merge —
  each steady-state round costs ``max(comm, drain)`` instead of their
  sum, and with k > 2 the ring absorbs a multi-round incast spike: up
  to k-1 exchanged windows can queue while one slow drain (or k-1
  drains while one slow exchange) catches up
  (``cost_model.pipeline_span`` is the exact makespan recurrence).
* **epilogue** — the last ``depth-1`` carried windows drain; nothing
  is exchanged.

Buffer ownership: the exchanged-but-undrained windows (``rx`` tuples
of post-``all_to_all`` buckets) are the loop carry — a ring of
``depth-1`` tuples rotated each iteration, plus the buffer the current
exchange refills, so exactly ``min(depth, n_rounds)``
``n_nodes * min(data_cap, cb)`` receive images are ever live — the
k x window memory price (``peak_aggregator_buffer_elems`` with
``pipeline_depth=k``). Depth clamps to the round count.

Byte-identity: the ring only re-associates WHEN each round's drain
runs, not WHAT it drains — every round's received buckets pass through
the identical drain (same sort, same pack base ``t * cb``, same pmax
merge) exactly once, and rounds still accumulate into disjoint
``[t*cb, (t+1)*cb)`` slices of the domain buffer, so the result is
bit-identical to the serial loop for EVERY depth (asserted by
``repro/testing/rounds_checks.py`` for depths {1, 2, 3, 4} x round
counts {1, 2, 5}).

Round-aware TAM stage 1
-----------------------
:func:`exchange_rounds_write_tam` fuses BOTH TAM layers into the same
window loop: per round, ranks ship only the window's requests to their
local aggregator (the ``lmem`` gather is bounded at
``min(data_cap, cb)`` per rank instead of ``data_cap``), the LA
sorts/coalesces that window, and the coalesced window flows through the
same slow-axis exchange + pmax drain. Local-aggregator memory is then
``ranks_per_node * min(data_cap, cb)`` — O(cb) for cb < data_cap —
instead of ``ranks_per_node * data_cap`` (the ``tam_stage1_*`` keys of
:func:`peak_aggregator_buffer_elems`).

Semantics: concurrently written regions must not overlap (the MPI
standard leaves overlapping collective writes undefined); when they do,
the masked max-combine resolves each element deterministically to the
maximum written value, and capacity overflow is reported through the
``dropped_requests`` / ``dropped_elems`` stats, never silent.

Stage scopes and slow-hop counters
----------------------------------
Every stage of the round loop runs under a flat ``jax.named_scope``,
so each compiled op's ``op_name`` metadata names the stage it belongs
to (its innermost ``io.`` component): ``io.split`` (the once-per-write
split, starts, destinations and windows), ``io.select`` (the per-round
compaction of the window's requests and its payload repack),
``io.stage1`` (TAM's intra-node gather, sort, repack, coalesce and
re-split), ``io.exchange`` (bucketing, the codec's encode and the
slow-axis ``all_to_all``), ``io.drain`` (decode, flatten, sort and
pack of a received window), ``io.merge`` (the masked ``pmax`` merge
and the accumulate at ``t * cb``); the read has ``io.index`` (the
once-per-read element index and window runs), ``io.fetch`` (the window
broadcast) and ``io.scatter`` (placing the window's elements, under a
nested ``sliced`` or ``full_pass`` scope naming the path the read
took). The scopes are metadata only: they add no op. Both writes also
count, per round, the requested payload elements the slow-axis
``all_to_all`` carries (``slow_hop_live_elems``) and the elements it
moves, padded buckets of every wire part included
(``slow_hop_shipped_elems``).

Cost-model coupling
-------------------
The executed round count is ``RoundScheduler.n_rounds`` ==
``cost_model.Workload.rounds`` when ``rounds_override`` is wired from a
measured run (``IOTimings.rounds_executed`` on the host path). Each
round pays ``alpha_eff(senders)`` once (incast refinement 2), which is
exactly what ``HostCollectiveIO.write(cb_bytes=...)`` times; with
``pipeline=True`` the steady-state rounds overlap that latency with the
drain (refinement 4), and ``cost_model.optimal_cb`` picks the cb
balancing incast latency, memory, and round count.
"""
from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import coalesce as co
from repro.core import codec as codec_mod
from repro.core import placement as placement_mod
from repro.core.exchange import (bucket_by_dest, flatten_buckets,
                                 repack_sorted, sort_with)
# RoundScheduler folded into the plan IR (PR 3); re-exported here so
# ``from repro.core.rounds import RoundScheduler`` keeps working.
from repro.core.plan import RoundScheduler  # noqa: F401
from repro.core.requests import (PAD_OFFSET, RequestList, compact,
                                 segment_ids, split_at_stripes)


def _codec_hooks(slow_hop_codec: str | None, dtype, state_shape,
                 fused: bool = False):
    """(encode, decode, state0) for the slow-hop wire transform.

    ``encode(data, state) -> (wire_parts, state)`` runs inside the
    ``exchange`` closure BEFORE the slow-axis collective;
    ``decode(wire_parts) -> data`` runs inside the drain. ``state0`` is
    the codec's residual (error feedback) — the empty pytree for
    stateless codecs — and is threaded through the round loop by
    ``_run_rounds`` exactly like the in-flight ``rx`` windows. A lossy
    codec on a non-float payload dies here, at trace time.

    ``fused`` (``IOPlan.kernel_fusion == "fused_round"``) swaps the rle
    codec's stable-argsort compaction for the Pallas zero-skip kernel
    (``kernels.fused_round.zero_skip_encode``) and its staged decode
    scatter for ``zero_skip_decode`` — byte-identical wire and window,
    one VMEM block per bucket instead of an argsort (resp. an HBM
    staging buffer) per round. The decode half serves both directions:
    the write drain and the read fetch.
    """
    if slow_hop_codec is None:
        return (lambda data, st: ((data,), st),
                lambda parts: parts[0], ())
    c = codec_mod.get_codec(slow_hop_codec)
    if not c.lossless and not jnp.issubdtype(dtype, jnp.floating):
        raise TypeError(
            f"slow_hop_codec={c.name!r} is lossy (float payloads only) "
            f"but the payload dtype is {jnp.dtype(dtype)}")
    state0 = c.jax_init_state(state_shape, dtype) if c.stateful else ()
    if fused and c.name == "rle":
        from repro.kernels import ops as kops

        def enc(data, st):
            return kops.rle_zero_skip_encode(data), st

        def dec(parts):
            return kops.rle_zero_skip_decode(parts)

        return enc, dec, state0
    return c.jax_encode, c.jax_decode, state0


def _placement_hooks(placement, n_dest: int, dl: int, node_axis: str):
    """(to_slot, base0, unpermute) for an aggregator placement.

    ``to_slot(domain_idx)`` maps each request's destination DOMAIN to
    the SLOT serving it (``plan.placement``); ``base0`` is this slot's
    served domain's base offset (slot s serves domain ``inv[s]``); and
    ``unpermute(x)`` ppermutes the finished domain shards (and their
    per-aggregator stats) back into domain order — slot s holds domain
    ``inv[s]`` after the rounds, and sending it to slot ``inv[s]``
    leaves every slot holding its own domain, so the OUTPUT is
    byte-identical to the identity placement (the permutation moves
    where the aggregation work happens, never what lands in the file).
    The identity placement compiles the placement machinery away
    entirely.
    """
    if placement_mod.is_identity(placement):
        return (lambda d: d,
                lax.axis_index(node_axis) * dl,
                lambda x: x)
    perm = placement_mod.validate_placement(placement, n_dest)
    inv = placement_mod.inverse_placement(perm)
    perm_arr = jnp.asarray(perm, jnp.int32)
    inv_arr = jnp.asarray(inv, jnp.int32)

    def to_slot(domain_idx):
        return perm_arr[jnp.clip(domain_idx, 0, n_dest - 1)]

    base0 = inv_arr[lax.axis_index(node_axis)] * dl
    pairs = [(s, inv[s]) for s in range(n_dest)]

    def unpermute(x):
        return lax.ppermute(x, node_axis, pairs)

    return to_slot, base0, unpermute


def _effective_depth(pipeline: bool, depth: int | None) -> int:
    """Resolve the (pipeline, depth) sugar: an explicit ``depth`` wins;
    the ``pipeline`` bool alone means the classic double buffer."""
    if depth is not None:
        return max(1, int(depth))
    return 2 if pipeline else 1


# Stage scopes (module docstring): flat, so an op's innermost ``io.``
# component is its stage.
SPLIT, SELECT, STAGE1, EXCHANGE, DRAIN, MERGE = (
    "io.split", "io.select", "io.stage1", "io.exchange", "io.drain",
    "io.merge")
INDEX, FETCH, SCATTER = "io.index", "io.fetch", "io.scatter"


def _slow_hop_counts(r: RequestList, b, wire) -> tuple:
    """(live, shipped) elements of one rank's slow-axis exchange:
    payload of ``r`` placed in the buckets ``b``, and every element of
    the wire parts the ``all_to_all`` moves, padding included."""
    placed = jnp.sum(jnp.where(r.valid_mask(), r.lengths, 0),
                     dtype=jnp.int32) - b.dropped_elems
    return placed, jnp.int32(sum(p.size for p in wire))


def _compact_active(r: RequestList, starts: jax.Array, dest: jax.Array,
                    active: jax.Array):
    """Move the active requests to the front, preserving offset order
    (the inactive tail holds padding requests, with start and dest 0)."""
    off, ln, starts, dest = compact(active, r.offsets, r.lengths, starts,
                                    dest)
    count = jnp.sum(active, dtype=jnp.int32)
    live = jnp.arange(active.shape[0], dtype=jnp.int32) < count
    return (RequestList(jnp.where(live, off, PAD_OFFSET), ln, count),
            starts, dest)


def _lowest(dtype) -> jax.Array:
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.array(-jnp.inf, dtype)
    return jnp.array(jnp.iinfo(dtype).min, dtype)


def _make_drain(base0, cb: int, merge_axes: tuple[str, ...], dtype,
                decode=None, fused: bool = False):
    """Drain closure: merge one round's received buckets into the
    carried domain buffer (decode wire → flatten → sort → pack window →
    masked pmax merge → accumulate at ``t * cb``). ``rx`` is
    ``(offsets, lengths, counts, *wire_parts)``; ``decode`` inverts the
    slow-hop codec's encode (identity when no codec is planned).

    ``fused`` (``IOPlan.kernel_fusion == "fused_round"``) runs the sort
    + dual pack as ONE Pallas kernel (``kernels.fused_round``) instead
    of a stable argsort plus two scatter packs — byte-identical by the
    rounds_checks contract, one HBM round-trip instead of three."""
    low = _lowest(dtype)

    def drain(t, buf, rx):
        with jax.named_scope(DRAIN):
            data = (rx[3] if decode is None
                    else decode(rx[3:]).astype(dtype))
            merged, starts_m, data_flat = flatten_buckets(rx[0], rx[1],
                                                          rx[2], data)
            base = base0 + t * cb
            if fused:
                from repro.kernels import ops as kops
                win, mask = kops.fused_drain_pack(merged, starts_m,
                                                  data_flat, base, cb)
            else:
                sorted_r, starts_s = sort_with(merged, starts_m)
                win = co.pack_data(sorted_r, starts_s, data_flat, cb,
                                   base=base)
                mask = co.pack_data(sorted_r, starts_s,
                                    jnp.ones_like(data_flat), cb,
                                    base=base)
        with jax.named_scope(MERGE):
            comb = lax.pmax(jnp.where(mask != 0, win, low), merge_axes)
            anyw = lax.pmax(mask, merge_axes)
            final = jnp.where(anyw != 0, comb, jnp.zeros((), dtype))
            buf = lax.dynamic_update_slice(buf, final, (t * cb,))
        return buf, (merged.count,)

    return drain


def _run_rounds(n_rounds: int, domain_len: int, dtype, exchange, drain,
                n_ex_stats: int, n_dr_stats: int, depth: int,
                codec_state=()):
    """Drive the round loop: serial (depth 1) or a depth-k window ring.

    ``exchange(t, cstate) -> (rx, ex_stats, cstate)`` produces round
    t's received buckets and the advanced codec state (the slow-hop
    codec's residual — the empty pytree when stateless);
    ``drain(t, buf, rx) -> (buf, dr_stats)`` merges the buckets into
    the domain buffer. Stats tuples are accumulated elementwise.
    Ring schedule (depth k, clamped to the round count): the prologue
    exchanges rounds 0..k-2 into the ring (statically unrolled); the
    steady-state iteration t exchanges round t while draining the
    oldest carried window, round t-(k-1); the epilogue drains the
    remaining k-1 windows. Every round is drained exactly once, in
    order, through the identical drain — byte-identical to serial for
    every k. The codec state rides the same loop carry as the ring:
    exchanges always run in round order, so error feedback sees rounds
    0, 1, 2, ... at every depth.
    """
    zeros = tuple(jnp.int32(0) for _ in range(n_ex_stats + n_dr_stats))

    def add(acc, delta, base):
        return tuple(a + d for a, d in zip(acc[base:base + len(delta)],
                                           delta))

    buf0 = jnp.zeros((domain_len,), dtype)
    d = max(1, min(depth, n_rounds))
    if d == 1:
        def body(t, carry):
            buf, cst, acc = carry
            rx, ex, cst = exchange(t, cst)
            buf, dr = drain(t, buf, rx)
            return buf, cst, add(acc, ex, 0) + add(acc, dr, n_ex_stats)

        buf, _, acc = lax.fori_loop(0, n_rounds, body,
                                    (buf0, codec_state, zeros))
        return buf, acc[:n_ex_stats], acc[n_ex_stats:]

    ring: list = []                              # prologue: fill the ring
    acc = zeros
    cst = codec_state
    for i in range(d - 1):
        rx, ex, cst = exchange(i, cst)
        ring.append(rx)
        acc = add(acc, ex, 0) + acc[n_ex_stats:]

    def body(t, carry):
        buf, ring, cst, acc = carry
        rx_new, ex, cst = exchange(t, cst)       # refill the freed buffer …
        buf, dr = drain(t - (d - 1), buf, ring[0])   # … drain the oldest
        ring = ring[1:] + (rx_new,)
        return (buf, ring, cst,
                add(acc, ex, 0) + add(acc, dr, n_ex_stats))

    buf, ring, _, acc = lax.fori_loop(d - 1, n_rounds, body,
                                      (buf0, tuple(ring), cst, acc))
    for j in range(d - 1):                       # epilogue: drain the ring
        buf, dr = drain(n_rounds - (d - 1) + j, buf, ring[j])
        acc = acc[:n_ex_stats] + add(acc, dr, n_ex_stats)
    return buf, acc[:n_ex_stats], acc[n_ex_stats:]


def exchange_rounds_write(sched: RoundScheduler, node_axis: str,
                          merge_axes: tuple[str, ...], r: RequestList,
                          starts: jax.Array, data: jax.Array,
                          pipeline: bool = False,
                          depth: int | None = None,
                          slow_hop_codec: str | None = None,
                          placement=None,
                          kernel_fusion: str | None = None):
    """Round loop of the collective write (runs inside a shard_map body).

    r/starts/data: this sender's offset-sorted requests, the payload
    start of each request inside ``data``, and the packed payload.
    ``depth=k`` runs the depth-k window ring (k in-flight windows;
    byte-identical to the serial loop for every k — see the module
    docstring); ``pipeline=True`` is sugar for depth 2.
    ``slow_hop_codec`` names a ``core.codec`` transform applied to each
    round's payload buckets around the slow-axis ``all_to_all``
    (lossless codecs keep byte identity; ``ef-int8``'s residual rides
    the loop carry). ``placement`` is the plan's aggregator permutation
    (``core.placement``): requests route to the slot SERVING their
    domain and the finished shards ppermute back into domain order, so
    the output is byte-identical for every placement. Returns
    (domain shard [domain_len], stats dict); ``requests_at_ga`` is
    already summed over ``merge_axes`` (replicated at the node) and
    reported in DOMAIN order whatever the placement; the
    ``slow_hop_*_elems`` counts are this rank's, summed over rounds.
    ``kernel_fusion="fused_round"`` (``IOPlan.kernel_fusion``, set by
    the planner's ``lower_kernels`` pass) drains each window with the
    single fused Pallas kernel and, when the codec is rle, encodes the
    wire with the fused zero-skip kernel — byte-identical either way.
    """
    fused = kernel_fusion == "fused_round"
    n_dest, cb, dl = sched.n_aggregators, sched.cb, sched.domain_len
    data_cap = data.shape[0]
    with jax.named_scope(SPLIT):
        split = split_at_stripes(r, cb, sched.max_spans(data_cap),
                                 data_cap)
        s_starts = co.request_starts(split)
        to_slot, base0, unpermute = _placement_hooks(placement, n_dest,
                                                     dl, node_axis)
        dest = to_slot((split.offsets // dl).astype(jnp.int32))
        window = sched.window_of(split.offsets)
    round_req_cap = min(split.capacity, cb)
    round_data_cap = min(data_cap, cb)
    # a round's payload fills at most one cb window per destination:
    # repacking only that much keeps a round's work O(window), not
    # O(data_cap) (any excess is over a bucket's cap and counted dropped)
    round_in_cap = min(data_cap, n_dest * round_data_cap)
    a2a = partial(lax.all_to_all, axis_name=node_axis, split_axis=0,
                  concat_axis=0, tiled=True)
    enc, dec, cstate0 = _codec_hooks(slow_hop_codec, data.dtype,
                                     (n_dest, round_data_cap),
                                     fused=fused)

    def exchange(t, cst):
        with jax.named_scope(SELECT):
            active = split.valid_mask() & (window == t)
            act_r, act_starts, act_dest = _compact_active(split, s_starts,
                                                          dest, active)
            act_data = repack_sorted(act_r, act_starts, data, round_in_cap)
        with jax.named_scope(EXCHANGE):
            b = bucket_by_dest(act_r, co.request_starts(act_r), act_data,
                               act_dest, n_dest, round_req_cap,
                               round_data_cap)
            wire, cst = enc(b.data, cst)
            rx = ((a2a(b.offsets), a2a(b.lengths), a2a(b.counts))
                  + tuple(a2a(p) for p in wire))
            live, shipped = _slow_hop_counts(act_r, b, wire)
        return rx, (b.dropped_requests, b.dropped_elems, live, shipped), cst

    drain = _make_drain(base0, cb, merge_axes, data.dtype, decode=dec,
                        fused=fused)
    buf, (drop_r, drop_e, live, shipped), (reqs_rx,) = _run_rounds(
        sched.n_rounds, dl, data.dtype, exchange, drain, 4, 1,
        _effective_depth(pipeline, depth), codec_state=cstate0)
    return unpermute(buf), {
        "dropped_requests": drop_r,
        "dropped_elems": drop_e,
        "slow_hop_live_elems": live,
        "slow_hop_shipped_elems": shipped,
        "requests_at_ga": unpermute(lax.psum(reqs_rx, merge_axes)),
    }


def exchange_rounds_write_tam(sched: RoundScheduler, node_axis: str,
                              lagg_axis: str, lmem_axis: str,
                              r: RequestList, starts: jax.Array,
                              data: jax.Array,
                              coalesce_cap: int | None = None,
                              use_kernels: bool = False,
                              pipeline: bool = False,
                              depth: int | None = None,
                              slow_hop_codec: str | None = None,
                              placement=None,
                              kernel_fusion: str | None = None):
    """Fused TAM round loop: BOTH aggregation layers run per window.

    Per round t, stage 1 gathers only the window's requests over
    ``lmem_axis`` (per-rank payload bounded at ``min(data_cap, cb)``),
    the local aggregator sorts/coalesces/repacks that window, and
    stage 2 exchanges the coalesced window over ``node_axis`` with the
    pmax merge over ``lagg_axis`` — so local-aggregator memory is
    O(cb) too, not just the global aggregator's (ROADMAP item).
    ``depth=k`` / ``pipeline=True`` overlap each round's two-layer
    exchange with older rounds' drains through the depth-k window
    ring, as in :func:`exchange_rounds_write`.

    Returns (domain shard, stats). ``*_rank`` drop stats are per-rank
    (pre-gather — psum over all axes); ``*_agg`` drops and the
    before/after coalesce counts are replicated across ``lmem_axis``
    (post-gather — divide the psum by the lmem size), and so is
    ``slow_hop_live_elems``; ``slow_hop_shipped_elems`` counts what this
    rank's own ``all_to_all`` moves, copies included.
    ``kernel_fusion="fused_round"`` fuses the global-aggregator drain
    (and the rle wire encode) exactly as in
    :func:`exchange_rounds_write`.
    """
    fused = kernel_fusion == "fused_round"
    n_dest, cb, dl = sched.n_aggregators, sched.cb, sched.domain_len
    data_cap = data.shape[0]
    with jax.named_scope(SPLIT):
        split = split_at_stripes(r, cb, sched.max_spans(data_cap),
                                 data_cap)
        s_starts = co.request_starts(split)
        dest0 = (split.offsets // dl).astype(jnp.int32)
        window = sched.window_of(split.offsets)
    rcap = min(split.capacity, cb)       # stage-1 requests/rank/round
    rdcap = min(data_cap, cb)            # stage-1 payload/rank/round
    # placement routes only the SLOW hop (stage 2): the intra-node
    # gather is placement-blind, mirroring the codec's asymmetry
    with jax.named_scope(SPLIT):
        to_slot, base0, unpermute = _placement_hooks(placement, n_dest,
                                                     dl, node_axis)
    a2a = partial(lax.all_to_all, axis_name=node_axis, split_axis=0,
                  concat_axis=0, tiled=True)
    g = partial(lax.all_gather, axis_name=lmem_axis, axis=0, tiled=False)
    idx = jnp.arange(split.capacity, dtype=jnp.int32)
    # the codec wraps ONLY the slow-axis hop (stage 2): the intra-node
    # gather stays raw — exactly the paper's asymmetry (compress where
    # the fabric is slow), mirroring hierarchical.compressed_psum
    lmem_size = lax.axis_size(lmem_axis)
    enc, dec, cstate0 = _codec_hooks(
        slow_hop_codec, data.dtype,
        (n_dest, min(lmem_size * rdcap, cb)), fused=fused)

    def exchange(t, cst):
        with jax.named_scope(SELECT):
            active = split.valid_mask() & (window == t)
            act_r, act_starts, _ = _compact_active(split, s_starts, dest0,
                                                   active)
            drop_rank_r = jnp.maximum(act_r.count - rcap, 0)
            drop_rank_e = jnp.sum(jnp.where(idx >= rcap, act_r.lengths, 0),
                                  dtype=jnp.int32)
            win_r = RequestList(act_r.offsets[:rcap], act_r.lengths[:rcap],
                                jnp.minimum(act_r.count, rcap))
            drop_rank_e = drop_rank_e + jnp.maximum(
                jnp.sum(win_r.lengths, dtype=jnp.int32) - rdcap, 0)
            win_data = repack_sorted(win_r, act_starts[:rcap], data, rdcap)
        # ---- stage 1: window-bounded intra-node aggregation -------------
        with jax.named_scope(STAGE1):
            all_off, all_len, all_cnt, all_data = (
                g(win_r.offsets), g(win_r.lengths), g(win_r.count),
                g(win_data))
            m = all_off.shape[0]
            merged, starts_m, data_flat = flatten_buckets(
                all_off, all_len, all_cnt, all_data)
            if use_kernels:
                from repro.kernels import ops as kops
                sorted_r, starts_s = kops.sort_requests_with(merged,
                                                             starts_m)
                packed = repack_sorted(sorted_r, starts_s, data_flat,
                                       m * rdcap)
                coal = kops.coalesce(sorted_r)
            else:
                sorted_r, starts_s = sort_with(merged, starts_m)
                packed = repack_sorted(sorted_r, starts_s, data_flat,
                                       m * rdcap)
                coal = co.coalesce_sorted(sorted_r)
            ccap = min(coalesce_cap or coal.capacity, coal.capacity)
            drop_agg_r = jnp.maximum(coal.count - ccap, 0)
            agg = RequestList(coal.offsets[:ccap], coal.lengths[:ccap],
                              jnp.minimum(coal.count, ccap))
            # a coalesced run can escape its window only when cb == dl
            # (the last window of domain d touches window 0 of domain
            # d+1, both live in the single round) — re-split at the
            # domain boundary so each forwarded request has exactly one
            # owner
            agg = split_at_stripes(agg, dl, m * rdcap // dl + 2)
        # ---- stage 2: slow-axis exchange of the coalesced window --------
        with jax.named_scope(EXCHANGE):
            dest = to_slot((agg.offsets // dl).astype(jnp.int32))
            b = bucket_by_dest(agg, co.request_starts(agg), packed, dest,
                               n_dest, min(agg.capacity, cb),
                               min(m * rdcap, cb))
            wire, cst = enc(b.data, cst)
            rx = ((a2a(b.offsets), a2a(b.lengths), a2a(b.counts))
                  + tuple(a2a(p) for p in wire))
            live, shipped = _slow_hop_counts(agg, b, wire)
        return rx, (drop_rank_r, drop_rank_e,
                    b.dropped_requests + drop_agg_r, b.dropped_elems,
                    merged.count, agg.count, live, shipped), cst

    drain = _make_drain(base0, cb, (lagg_axis,), data.dtype, decode=dec,
                        fused=fused)
    buf, ex_acc, dr_acc = _run_rounds(
        sched.n_rounds, dl, data.dtype, exchange, drain, 8, 1,
        _effective_depth(pipeline, depth), codec_state=cstate0)
    (drop_rank_r, drop_rank_e, drop_agg_r, drop_agg_e,
     n_before, n_after, live, shipped) = ex_acc
    return unpermute(buf), {
        "dropped_requests_rank": drop_rank_r,
        "dropped_elems_rank": drop_rank_e,
        "dropped_requests_agg": drop_agg_r,
        "dropped_elems_agg": drop_agg_e,
        "requests_before_coalesce": n_before,
        "requests_after_coalesce": n_after,
        "slow_hop_live_elems": live,
        "slow_hop_shipped_elems": shipped,
        "requests_at_ga": unpermute(lax.psum(dr_acc[0], (lagg_axis,))),
    }


class ReadWindowRuns(NamedTuple):
    """Where each (domain, window) of a read lands in one rank's output
    (:func:`read_window_runs`). Window ``k = g * n_rounds + t`` is
    round ``t``'s window of domain ``g``: file positions
    ``[k * cb, (k + 1) * cb)``.

    fpos: int32[data_cap] — the file position of each output element;
        ``file_len`` past the payload (window key ``n_windows``).
    sliceable: bool scalar — file positions rise strictly over the
        payload and stay inside the file (a list sorted by offset whose
        requests are disjoint); only then does ``first`` describe the
        output.
    first: int32[n_windows + 1] — the output index of each window's
        first element (``first[n_windows]`` is where the payload ends);
        window k fills ``first[k + 1] - first[k]`` elements.
    """

    fpos: jax.Array
    sliceable: jax.Array
    first: jax.Array


def read_slices_pay(sched: RoundScheduler, data_cap: int) -> bool:
    """Whether the read's sliced scatter, ``n_aggregators`` slices of
    ``min(cb, data_cap)`` elements per round, touches fewer elements
    than a pass over the ``data_cap``-element output."""
    return sched.n_aggregators * min(sched.cb, data_cap) < data_cap


def read_window_runs(r: RequestList, starts: jax.Array,
                     sched: RoundScheduler, data_cap: int
                     ) -> ReadWindowRuns:
    """The file position of each of one rank's read output elements,
    and the run of the output each (domain, window) fills. Where file
    positions rise strictly, a window's elements are one contiguous run
    of at most ``min(cb, data_cap)`` elements (its positions are
    distinct), found by a binary search of the window starts in the
    positions. ``starts`` are the requests' payload starts
    (``coalesce.request_starts``). Keep the predicate and the search on
    ``fpos``: on a v5e, a second reader of the request list moved its
    gather table out of fast memory and made the positions' gather
    2.5x slower."""
    cb, file_len = sched.cb, sched.layout.file_len
    n_windows = sched.n_aggregators * sched.n_rounds
    eidx = jnp.arange(data_cap, dtype=jnp.int32)
    req_of = segment_ids(r.lengths, data_cap)
    fpos = r.offsets[req_of] + (eidx - starts[req_of])
    live = eidx < jnp.sum(r.lengths, dtype=jnp.int32)
    fpos = jnp.where(live, fpos, file_len)
    sliceable = (jnp.all(~live[1:] | (fpos[1:] > fpos[:-1]))
                 & jnp.all(~live | ((fpos >= 0) & (fpos < file_len))))
    first = jnp.searchsorted(
        fpos, jnp.arange(n_windows + 1, dtype=jnp.int32) * cb)
    return ReadWindowRuns(fpos, sliceable, first)


def exchange_rounds_read(sched: RoundScheduler, node_axis: str,
                         r: RequestList, starts: jax.Array,
                         file_shard: jax.Array, data_cap: int,
                         pipeline: bool = False,
                         depth: int | None = None,
                         slow_hop_codec: str | None = None,
                         placement=None,
                         kernel_fusion: str | None = None, *,
                         rank_axes: tuple[str, ...]) -> jax.Array:
    """Round loop of the collective read: per round, aggregators
    broadcast one ``cb``-sized window over the slow axis and every rank
    places the elements of its requests falling in that window. Peak
    per-rank buffering is ``n_nodes * cb`` instead of ``file_len``.
    ``depth=k`` / ``pipeline=True`` run the window ring: the broadcast
    of window t overlaps the scatters of the k-1 carried older windows.
    ``slow_hop_codec`` encodes each aggregator's window before the
    slow-axis broadcast and decodes after (per-window, residual-free:
    a broadcast repeats nothing, so error feedback has nothing to
    correct — ``ef-int8`` here is plain per-window quantization).
    ``placement`` permutes which slot SERVES (broadcasts) each domain:
    the file shards ppermute to their serving slots up front and ranks
    index the gathered windows through the permutation — the returned
    payloads are byte-identical for every placement.
    ``kernel_fusion="fused_round"`` swaps the rle decode scatter for
    the Pallas ``zero_skip_decode`` kernel (byte-identical; execution
    strategy only, never routing). ``rank_axes`` are the mesh axes the
    ranks span.

    The scatter takes one of two paths, the same for the whole read:

    * **sliced** (scope ``io.scatter/sliced``): MPI file views have
      nondecreasing displacements, so a rank's flattened request list
      is sorted by offset. Where its requests are also disjoint, file
      positions rise along the output, and each (domain, window) fills
      one contiguous run of it (:func:`read_window_runs`, once, in
      ``io.index``). Round t places domain g's window through a
      ``min(cb, data_cap)``-element slice of the output at that run's
      start, so a round touches ``n_dest * min(cb, data_cap)``
      elements, not ``data_cap``.
    * **full pass** (scope ``io.scatter/full_pass``): every round
      selects over the whole output.

    The shapes pick first, at trace time: where the slices would touch
    no fewer elements than the full pass (:func:`read_slices_pay`
    false, e.g. one round of ``cb = domain_len`` over a per-rank output
    no longer than ``n_dest * cb``), only the full pass is built. Else
    one ``lax.cond`` outside the round loop picks, at run time, the
    sliced path when every rank over ``rank_axes`` can take it, so all
    ranks run the same collectives; a rank whose own read requests
    overlap (the sorted lists MPI allows) takes every rank down the
    full pass. Both paths return the same bytes. Whether a read took
    the sliced path, and how full its slices are (``first[-1] -
    first[0]`` ÷ ``n_windows * min(cb, data_cap)``), is
    :func:`read_slices_pay` and :func:`read_window_runs` of the same
    requests.
    """
    n_dest, cb, dl = sched.n_aggregators, sched.cb, sched.domain_len
    n_rounds = sched.n_rounds
    with jax.named_scope(INDEX):
        if not placement_mod.is_identity(placement):
            perm = placement_mod.validate_placement(placement, n_dest)
            # slot perm[g] serves domain g: hand it the domain's shard
            file_shard = lax.ppermute(
                file_shard, node_axis, [(s, perm[s]) for s in range(n_dest)])
            slot_of = jnp.asarray(perm, jnp.int32)
        else:
            perm = tuple(range(n_dest))
            slot_of = None
        # the full pass alone reads only fpos: XLA drops the rest
        runs = read_window_runs(r, starts, sched, data_cap)
        fpos = runs.fpos

    enc, dec, _ = _codec_hooks(slow_hop_codec, file_shard.dtype, (cb,),
                               fused=kernel_fusion == "fused_round")

    @jax.named_scope(FETCH)
    def fetch(t):
        win = lax.dynamic_slice_in_dim(file_shard, t * cb, cb)
        if slow_hop_codec is None:
            return lax.all_gather(win, node_axis, axis=0, tiled=True)
        parts, _ = enc(win, ())      # broadcast: no residual to carry
        gathered = tuple(
            lax.all_gather(p, node_axis, axis=0, tiled=False)
            if p.ndim == 0 else
            lax.all_gather(p, node_axis, axis=0,
                           tiled=True).reshape(n_dest, *p.shape)
            for p in parts)
        return (dec(gathered).astype(file_shard.dtype).reshape(-1))

    d = max(1, min(_effective_depth(pipeline, depth), n_rounds))

    def run(scatter):
        out0 = jnp.zeros((data_cap,), file_shard.dtype)
        if d == 1:
            return lax.fori_loop(
                0, n_rounds, lambda t, out: scatter(t, out, fetch(t)), out0)
        ring = tuple(fetch(i) for i in range(d - 1))    # prologue

        def body(t, carry):
            out, ring = carry
            nxt = fetch(t)                           # broadcast window t …
            out = scatter(t - (d - 1), out, ring[0])    # … place the oldest
            return out, ring[1:] + (nxt,)

        out, ring = lax.fori_loop(d - 1, n_rounds, body, (out0, ring))
        for j in range(d - 1):                       # epilogue
            out = scatter(n_rounds - (d - 1) + j, out, ring[j])
        return out

    def full_pass():
        with jax.named_scope(INDEX):
            live = (jnp.arange(data_cap, dtype=jnp.int32)
                    < jnp.sum(r.lengths, dtype=jnp.int32))
            dest, wloc = fpos // dl, fpos % dl

        @jax.named_scope(SCATTER)
        @jax.named_scope("full_pass")
        def scatter(t, out, allw):
            active = live & (wloc // cb == t)
            slot = dest if slot_of is None else slot_of[dest]
            src = slot * cb + (wloc - t * cb)
            vals = allw[jnp.clip(src, 0, n_dest * cb - 1)]
            return jnp.where(active, vals, out)

        return run(scatter)

    if not read_slices_pay(sched, data_cap):
        return full_pass()

    span = min(cb, data_cap)

    @jax.named_scope(SCATTER)
    @jax.named_scope("sliced")
    def scatter_sliced(t, out, allw):
        for g in range(n_dest):
            k = g * n_rounds + t
            a, b = runs.first[k], runs.first[k + 1]
            s0 = jnp.clip(a, 0, data_cap - span)
            e = s0 + jnp.arange(span, dtype=jnp.int32)
            f = lax.dynamic_slice_in_dim(fpos, s0, span)
            src = perm[g] * cb + (f - k * cb)
            vals = allw[jnp.clip(src, 0, n_dest * cb - 1)]
            cur = lax.dynamic_slice_in_dim(out, s0, span)
            out = lax.dynamic_update_slice_in_dim(
                out, jnp.where((e >= a) & (e < b), vals, cur), s0, 0)
        return out

    with jax.named_scope(INDEX):
        sliced = lax.pmin(runs.sliceable.astype(jnp.int32), rank_axes) > 0
    return lax.cond(sliced, lambda: run(scatter_sliced), full_pass)


def peak_aggregator_buffer_elems(data_cap: int, n_nodes: int,
                                 ranks_per_node: int, domain_len: int,
                                 cb_buffer_size: int | None,
                                 pipeline: bool = False,
                                 pipeline_depth: int | None = None,
                                 slow_hop_codec: str | None = None) -> dict:
    """Static receive-side buffer sizes (elements) of the write paths.

    ``single_shot`` is the flattened payload stack after the slow-axis
    all_to_all plus the intra-node gather — linear in the participating
    rank count. ``rounds`` is the a2a slice plus one window image —
    independent of ``ranks_per_node`` (the acceptance criterion); with
    ``pipeline_depth=k`` (``pipeline=True`` is sugar for k=2) k a2a
    window buffers are in flight — the k x window memory price of the
    ring: the loop carry holds the k-1 oldest undrained rounds'
    received buckets while the current exchange fills the k-th (the
    depth clamps to the round count at run time; this static bound
    charges the configured k).
    ``tam_stage1_*`` are the local aggregator's intra-node gather
    buffers: the fused round loop (:func:`exchange_rounds_write_tam`)
    bounds the per-rank contribution at ``min(data_cap, cb)`` instead
    of ``data_cap``. Stage 1 is NOT multiplied by the ring depth: the
    gather is produced and consumed inside one exchange step, so only
    one is ever live — only the post-``all_to_all`` carry rings.
    ``slow_hop_codec`` scales the in-flight a2a windows by the codec's
    static wire width (``Codec.jax_wire_overhead`` — e.g. rle rings
    values AND int32 positions, 2x; XLA buffers cannot shrink, so the
    RING memory pays the wire format even though the WIRE volume the
    cost model discounts is smaller).
    """
    wire = (codec_mod.get_codec(slow_hop_codec).jax_wire_overhead
            if slow_hop_codec is not None else 1.0)
    single = n_nodes * ranks_per_node * data_cap + domain_len
    cb = cb_buffer_size if cb_buffer_size is not None else domain_len
    in_flight = _effective_depth(pipeline, pipeline_depth)
    rounds = (math.ceil(n_nodes * min(data_cap, cb) * wire)
              * in_flight + cb + domain_len)
    return {
        "single_shot": single,
        "rounds": rounds,
        "tam_stage1_single_shot": ranks_per_node * data_cap,
        "tam_stage1_rounds": ranks_per_node * min(data_cap, cb),
    }

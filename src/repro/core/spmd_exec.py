"""SPMD executor: runs a compiled :class:`repro.core.plan.IOPlan`.

One of the two interchangeable backends of the plan/executor split
(ARCHITECTURE.md); the other is ``repro.checkpoint.host_exec``. This
one lowers the plan to a ``shard_map`` program over the
``(node, lagg, lmem)`` mesh view and drives the depth-k round ring of
``repro.core.rounds``:

* ``method="twophase"`` — every rank routes each window's requests
  straight to the owning global aggregator (slow-axis ``all_to_all``)
  and the window merges with a masked pmax over the intra-node axes.
* ``method="tam"`` — both aggregation layers run inside the window
  loop (``exchange_rounds_write_tam``): the intra-node gather is
  bounded at ``min(data_cap, cb)`` per rank, then only the coalesced
  window crosses the slow axis.
* ``direction="read"`` — aggregators broadcast one cb window per round
  and ranks gather their own elements.

The single-shot exchange that used to live as a separate code path in
``twophase.py`` / ``tam.py`` is gone: a plan with ``cb == domain_len``
is a 1-round schedule and runs through the same ring (the round engine
with one window IS the single shot — asserted byte-identical by
``repro/testing/rounds_checks.py`` long before the paths merged).

The slow-hop codec (``plan.slow_hop_codec``, ``core.codec``) is such a
per-round transform, wrapped around the ``exchange``/``drain`` pair
inside ``core.rounds`` — both schedules and every depth inherit it;
see ARCHITECTURE.md § "The slow-hop codec".
"""
from __future__ import annotations

from functools import partial

import jax
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core import coalesce as co
from repro.core import rounds
from repro.core.plan import IOPlan, compile_plan
from repro.core.requests import RequestList, mask_invalid


def _as_requests(offsets, lengths, count) -> RequestList:
    return mask_invalid(RequestList(offsets.reshape(-1),
                                    lengths.reshape(-1),
                                    count.reshape(())))


def _write_shard_fn(plan: IOPlan, use_kernels: bool,
                    offsets, lengths, count, data):
    node, lagg, lmem = plan.axis_names
    r = _as_requests(offsets, lengths, count)
    data = data.reshape(-1)
    starts = co.request_starts(r)
    sched = plan.scheduler()

    if plan.method == "tam":
        # fused two-layer round loop; post-gather state is replicated
        # across lmem, so the window merge and receive stats run over
        # lagg only (the pmax combine is idempotent under that
        # replication) and replicated stats divide by the lmem size.
        shard, st = rounds.exchange_rounds_write_tam(
            sched, node, lagg, lmem, r, starts, data,
            coalesce_cap=plan.coalesce_cap, use_kernels=use_kernels,
            depth=plan.pipeline_depth,
            slow_hop_codec=plan.slow_hop_codec,
            placement=plan.placement,
            kernel_fusion=plan.kernel_fusion)
        lmem_size = lax.axis_size(lmem)
        all_axes = (node, lagg, lmem)
        stats = {
            "dropped_requests":
                lax.psum(st["dropped_requests_rank"], all_axes)
                + lax.psum(st["dropped_requests_agg"], all_axes)
                // lmem_size,
            "dropped_elems":
                lax.psum(st["dropped_elems_rank"], all_axes)
                + lax.psum(st["dropped_elems_agg"], all_axes)
                // lmem_size,
            "requests_before_coalesce": lax.psum(
                st["requests_before_coalesce"], all_axes) // lmem_size,
            "requests_after_coalesce": lax.psum(
                st["requests_after_coalesce"], all_axes) // lmem_size,
            "slow_hop_live_elems": lax.psum(
                st["slow_hop_live_elems"], all_axes) // lmem_size,
            "slow_hop_shipped_elems": lax.psum(
                st["slow_hop_shipped_elems"], all_axes),
            "requests_at_ga": st["requests_at_ga"][None],
        }
        return shard[None], stats

    shard, st = rounds.exchange_rounds_write(
        sched, node, (lagg, lmem), r, starts, data,
        depth=plan.pipeline_depth,
        slow_hop_codec=plan.slow_hop_codec,
        placement=plan.placement,
        kernel_fusion=plan.kernel_fusion)
    all_axes = (node, lagg, lmem)
    stats = {k: lax.psum(st[k], all_axes)
             for k in ("dropped_requests", "dropped_elems",
                       "slow_hop_live_elems", "slow_hop_shipped_elems")}
    stats["requests_at_ga"] = st["requests_at_ga"][None]
    return shard[None], stats


def _read_shard_fn(plan: IOPlan, offsets, lengths, count, file_shard):
    node = plan.axis_names[0]
    r = _as_requests(offsets, lengths, count)
    starts = co.request_starts(r)
    out = rounds.exchange_rounds_read(
        plan.scheduler(), node, r, starts, file_shard.reshape(-1),
        plan.data_cap, depth=plan.pipeline_depth,
        slow_hop_codec=plan.slow_hop_codec,
        placement=plan.placement,
        kernel_fusion=plan.kernel_fusion,
        rank_axes=plan.axis_names)
    return out[None]


def make_collective_write(mesh: jax.sharding.Mesh, layout, cfg,
                          method: str = "auto", use_kernels: bool = False,
                          machine=None, workload=None):
    """Plan + execute in one call, with ``method="auto"`` picking
    two-phase vs TAM per workload via the cost model at plan time
    (``tam_cost`` at the optimal P_L vs ``twophase_cost``). The stats
    dict follows the resolved method (TAM adds the coalesce counters).
    Pass a measured ``cost_model.Workload`` to ground the choice in
    observed request counts instead of the static capacities."""
    node = cfg.axis_names[0]
    plan = compile_plan(layout, cfg, n_aggregators=mesh.shape[node],
                        n_nodes=mesh.shape[node], n_ranks=mesh.size,
                        method=method, machine=machine, workload=workload)
    return make_spmd_executor(mesh, plan, use_kernels=use_kernels)


def make_spmd_executor(mesh: jax.sharding.Mesh, plan: IOPlan,
                       use_kernels: bool = False):
    """Lower an :class:`IOPlan` to a jit-able shard_map program.

    Write plans return ``(file [n_aggregators, domain_len] sharded over
    the slow axis, stats dict)``; read plans return per-rank payloads.
    Both writes' stats hold ``slow_hop_live_elems`` (requested payload
    elements the slow-axis ``all_to_all`` carries, each once however
    many ranks hold a copy) and ``slow_hop_shipped_elems`` (elements it
    moves: every rank's padded wire buckets, every round).
    The mesh's slow-axis size must match the plan's aggregator count —
    the plan IS the schedule, the mesh is just where it runs.
    """
    node, lagg, lmem = plan.axis_names
    if mesh.shape[node] != plan.n_aggregators:
        raise ValueError(
            f"plan compiled for {plan.n_aggregators} aggregators but mesh "
            f"axis {node!r} has size {mesh.shape[node]}")
    rank_spec = P((node, lagg, lmem))
    if plan.direction == "read":
        return jax.shard_map(
            partial(_read_shard_fn, plan), mesh=mesh, check_vma=False,
            in_specs=(rank_spec, rank_spec, rank_spec, P(node)),
            out_specs=rank_spec)
    stats_spec = {"dropped_requests": P(), "dropped_elems": P(),
                  "slow_hop_live_elems": P(), "slow_hop_shipped_elems": P(),
                  "requests_at_ga": P(node)}
    if plan.method == "tam":
        stats_spec.update({"requests_before_coalesce": P(),
                           "requests_after_coalesce": P()})
    return jax.shard_map(
        partial(_write_shard_fn, plan, use_kernels), mesh=mesh,
        check_vma=False,
        in_specs=(rank_spec, rank_spec, rank_spec, rank_spec),
        out_specs=(P(node), stats_spec))

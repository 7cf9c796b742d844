"""Shared workload registry for the benchmark suites.

One place for the paper's workload tables — the analytical generators
(Table I shapes for the cost model), the scaled host-path request
patterns, and the paper-scale constants — so ``benchmarks.run``'s
suites (``pipeline``, ``rounds``, ``paper_figures``) stop redefining
the e3sm_f / e3sm_g / btio / s3d parameter tables independently.
"""
from __future__ import annotations

from repro.core import cost_model as cm
from repro.core.requests import ELEM_BYTES
from repro.io_patterns import (btio_pattern, e3sm_f_pattern,
                               e3sm_g_pattern, s3d_pattern,
                               sparse_checkpoint_pattern)

# paper scale: P ranks / nodes / local aggregators (SV: 16384 cores,
# 256 Haswell nodes, P_L = one LA per node)
PAPER_P, PAPER_NODES, PAPER_P_L = 16384, 256, 256

# Table I analytical workloads: name -> Workload generator (P, nodes)
MODEL_WORKLOADS = {
    "e3sm_f": cm.e3sm_f,
    "e3sm_g": cm.e3sm_g,
    "btio": cm.btio,
    "s3d": cm.s3d,
}

# scaled host-path request generators: name -> (n_ranks -> rank_requests)
HOST_PATTERNS = {
    "e3sm_g": e3sm_g_pattern,
    "e3sm_f": e3sm_f_pattern,
    "btio": lambda P, n=32: btio_pattern(P, n=n),
    "s3d": lambda P, n=32: s3d_pattern(P, n=n),
    # zero-dominated checkpoint pages — the slow-hop codec's workload
    # (benchmarks/pipeline.py measures its wire ratio, CI gates it)
    "sparse_ckpt": sparse_checkpoint_pattern,
}


def paper_workload(name: str, P: int = PAPER_P,
                   nodes: int = PAPER_NODES) -> cm.Workload:
    """The named Table I workload at (P, nodes) — paper scale default."""
    return MODEL_WORKLOADS[name](P, nodes)


def node_share(name: str) -> tuple[int, int]:
    """One node's share of the named Table I workload at paper scale:
    ``(requests, bytes per request)``, the request size rounded down
    to whole 4-byte elements (E3SM-G: 679,688 requests of 524 B)."""
    w = paper_workload(name)
    n_req = w.k * w.P
    req_bytes = int(w.total_bytes / n_req) // ELEM_BYTES * ELEM_BYTES
    return round(n_req / w.nodes), req_bytes

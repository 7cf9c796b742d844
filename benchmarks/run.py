"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. Requires ``repro`` on the
path (``pip install -e .`` or ``PYTHONPATH=src``):

  PYTHONPATH=src python -m benchmarks.run [--only fig3]
"""
from __future__ import annotations

import argparse
from pathlib import Path

from benchmarks import (async_ckpt, degraded, kernel_bench, paper_figures,
                        pipeline, restore, rounds, spmd_bytes, transport)
from repro.launch.cache import enable_compilation_cache

SUITES = {
    "fig2": paper_figures.fig2_congestion,
    "fig3": paper_figures.fig3_bandwidth,
    "fig4_7": paper_figures.fig4_7_breakdown,
    "table1": paper_figures.table1_coalesce,
    "optimal_pl": paper_figures.optimal_pl_sweep,
    "kernels": kernel_bench.sort_coalesce_pack,
    "kernel_fusion": kernel_bench.fused_vs_unfused,
    "spmd_bytes": spmd_bytes.collective_bytes,
    "rounds": rounds.cb_sweep,
    "pipeline": pipeline.serial_vs_pipelined,
    "degraded": degraded.scenario_matrix,
    "restore": restore.replica_cache_sweep,
    "async_ckpt": async_ckpt.overlap_bench,
    "transport": transport.wire_sweep,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    enable_compilation_cache(Path(__file__).resolve().parents[1])
    print("name,us_per_call,derived")
    for name, fn in SUITES.items():
        if args.only and args.only != name:
            continue
        for row in fn():
            n, us, derived = row
            print(f"{n},{us:.2f},{derived}")


if __name__ == "__main__":
    main()

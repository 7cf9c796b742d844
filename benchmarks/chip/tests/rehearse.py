"""Run a rehearsal cell (``rehearsal/BENCHMARK.json``, tiny sizes) on the
CPU, once per ``--wrap`` (``none``, ``control`` or a fault of
``faults.py``), in one process:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \
    python3 benchmarks/chip/tests/rehearse.py \
        --workload e3sm_g_tiny_2x2.tam_cycle --seed 5 --seconds 0.2 \
        --trace 0 --wrap none control no_exchange

Each run prints its result line, preceded by a ``# wrap <name>`` line.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
CHIP = HERE.parent
sys.path[:0] = [str(HERE), str(CHIP), str(CHIP.parents[1] / "src")]

import control  # noqa: E402
import faults  # noqa: E402
import harness  # noqa: E402


def rehearsal_table() -> dict:
    """The real table's metrics over the rehearsal's configs and cells,
    each metric listed for every rehearsal cell."""
    table = harness.load_table()
    table.update(json.loads((HERE / "rehearsal" / "BENCHMARK.json")
                            .read_text()))
    for m in table["end_to_end"] + table["per_layer"]:
        m.pop("workloads", None)
    return table


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", required=True)
    ap.add_argument("--seconds", required=True)
    ap.add_argument("--trace", default="0")
    ap.add_argument("--wrap", nargs="+", default=["none"])
    args = ap.parse_args(argv)
    table = rehearsal_table()
    rc = 0
    for name in args.wrap:
        wrap = (None if name == "none" else control.control
                if name == "control" else getattr(faults, name))
        print(f"# wrap {name}", flush=True)
        rc |= harness.main(["--workload", args.workload, "--seed",
                            args.seed, "--seconds", args.seconds,
                            "--trace", args.trace],
                           t_start=time.perf_counter(), table=table,
                           allow_cpu=True, wrap=wrap)
    return rc


if __name__ == "__main__":
    sys.exit(main())

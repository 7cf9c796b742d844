"""The control of ``correct``: the plain reference put in the program's
place, with one guarantee the configuration states broken (every
rank's last request is dropped from the file). It must come out as not
correct.

    python3 benchmarks/chip/control.py --workload <name> --seconds <s> \
        --seeds <n> [<n> ...]

runs the cell once per seed in one process, with the control in place
of both programs, and prints each run's result line. The benchmark's
own runs never run it; ``tests/test_bench_rehearsal.py`` runs it at a
tiny size on the CPU.
"""
from __future__ import annotations

import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

import numpy as np  # noqa: E402

import yardstick as ys  # noqa: E402


def read_reference(file, offsets, lengths, counts, data_cap: int):
    """Host-side oracle of a collective read: every rank's requests
    gathered from the file, in request order."""
    file = np.asarray(file).reshape(-1)
    out = np.zeros((offsets.shape[0], data_cap), file.dtype)
    for p in range(offsets.shape[0]):
        pos = 0
        for i in range(counts[p]):
            o, l = int(offsets[p, i]), int(lengths[p, i])
            out[p, pos:pos + l] = file[o:o + l]
            pos += l
    return out


def control(cell) -> None:
    """Replace ``cell``'s programs by the reference with every rank's
    last request dropped."""
    import jax

    n_nodes = cell.layout.stripe_count
    dropped = cell.counts - 1

    def write(offsets, lengths, counts, data):
        f = ys.write_reference(cell.layout.file_len, cell.offsets,
                               cell.lengths, dropped, np.asarray(data))
        return jax.device_put(f.reshape(n_nodes, -1)), {}

    def read(offsets, lengths, counts, file):
        data_cap = cell.args[3].shape[1]
        return jax.device_put(read_reference(
            file, cell.offsets, cell.lengths, cell.counts, data_cap))

    cell.write, cell.read = write, read


def main(argv=None) -> int:
    import argparse

    import harness

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", required=True)
    ap.add_argument("--seeds", nargs="+", required=True)
    args = ap.parse_args(argv)
    rc = 0
    for seed in args.seeds:
        rc |= harness.main(["--workload", args.workload, "--seed", seed,
                            "--seconds", args.seconds, "--trace", "0"],
                           t_start=time.perf_counter(), wrap=control)
    return rc


if __name__ == "__main__":
    sys.exit(main())

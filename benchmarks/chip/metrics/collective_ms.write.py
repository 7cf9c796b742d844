"""Device time per write call of the collective ops (all-to-all,
all-reduce, all-gather, collective-permute and the like, by their HLO
names in the trace) inside the write programs' executions, averaged
over the chips (ms)."""
import statistics

import tracefile


def read(ctx):
    windows = ctx.call_windows("write")
    if windows is None:
        return None
    per_chip = tracefile.collective_ns(ctx.trace, windows)
    if not any(per_chip.values()):
        return None
    return statistics.fmean(per_chip.values()) / 1e6 / len(ctx.calls_of("write"))

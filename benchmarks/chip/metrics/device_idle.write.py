"""Share of the wall time of the write calls (host clock) in which no op
ran on the device inside the write programs' executions (profiler trace),
averaged over the chips (%)."""


def read(ctx):
    busy = ctx.device_busy_s("write")
    if busy is None:
        return None
    return 100.0 * (1.0 - busy / ctx.wall_s("write"))

"""Requested bytes of every collective write in the window over the
summed wall time of those writes, dispatch to ``block_until_ready``
(MB/s, 1e6 bytes)."""


def read(ctx):
    calls = ctx.calls_of("write")
    if not calls:
        return None
    return sum(c["bytes"] for c in calls) / sum(c["wall_s"] for c in calls) / 1e6

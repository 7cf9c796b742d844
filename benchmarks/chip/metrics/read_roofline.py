"""Least time the chip could take for the bytes one read needs
(roofline.io_bytes / least_time, from the requests and the file
layout) over the device time per read call from the trace (%)."""
import roofline


def read(ctx):
    busy = ctx.device_busy_s("read")
    if not busy:
        return None
    least, _ = roofline.least_time(ctx.work["read"], ctx.n_chips, ctx.peaks())
    return 100.0 * least / (busy / len(ctx.calls_of("read")))

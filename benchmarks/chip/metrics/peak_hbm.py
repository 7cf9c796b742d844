"""Peak device memory in use over the run (``memory_stats()``'s
``peak_bytes_in_use`` after the window, the fullest chip), in MiB."""


def read(ctx):
    if ctx.memory_peak_bytes is None:
        return None
    return ctx.memory_peak_bytes / 2**20

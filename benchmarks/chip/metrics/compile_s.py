"""Host seconds of ``.lower().compile()`` of both programs: a load from
the persistent cache on every run but a checkout's first."""


def read(ctx):
    return ctx.timings["compile_s"]

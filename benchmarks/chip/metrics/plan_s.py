"""Host seconds to plan both programs (the entry points' plan
compilation, ``compile_plan``, and the executor's construction)."""


def read(ctx):
    return ctx.timings["plan_s"]

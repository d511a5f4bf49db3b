"""Host clock around ``.lower().compile()`` of the step: a compilation
on a cold run, a cache read on a warm one."""


def read(ctx):
    return ctx.timeline["compile_s"]

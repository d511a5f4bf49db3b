"""Process start to ``hvd.init()`` returned, the plan made and the
first device array ready."""


def read(ctx):
    return ctx.timeline["init_s"]

"""Wall clock of the child that makes the checkpoint from the seed."""


def read(ctx):
    return ctx.timings.get("ckpt_make_s")

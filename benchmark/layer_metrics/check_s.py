"""Wall clock of the reference check's child, which runs beside the
checkpoint maker before the server starts: what it adds to set-up is its
excess over ``ckpt_make_s``."""


def read(ctx):
    return ctx.timings.get("check_s")

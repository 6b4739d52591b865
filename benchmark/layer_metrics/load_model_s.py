"""Wall clock of the request that makes the model manager spawn the runner
and load the model (LoadModel: weights, engine, the precompile ladder), as
the client sees it."""


def read(ctx):
    return ctx.timings.get("load_model_s")

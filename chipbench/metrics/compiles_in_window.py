"""Backend compiles (jax.monitoring events) between window open and close;
every shape is warmed in set-up, so this should read 0."""


def read(ctx):
    return float(ctx.window.compiles)

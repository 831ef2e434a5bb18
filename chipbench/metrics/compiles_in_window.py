"""Compile requests (jax.monitoring events) between window open and close,
those the persistent cache answered included; every shape is warmed in
set-up, so this should read 0. A gang's new engine lowering a program that
set-up built, after a resize, counts in that event's entry instead."""


def read(ctx):
    return float(ctx.window.compiles)

"""Compilation: backend compiles plus persistent-cache loads inside the
window, counted by JAX's monitoring events."""


def read(ctx):
    return ctx.compiles_in_window

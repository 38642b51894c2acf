"""compiles_in_window: programs compiled, or loaded from the persistent
compilation cache, inside the window (``jax.monitoring`` backend-compile
events).  Every shape is warmed in set-up, so this should be 0."""


def read(ctx):
    return ctx.compiles_in_window

"""Backend compiles (persistent-cache loads included) that happened
inside the measured window, from ``jax.monitoring``."""


def read(ctx):
    return float(ctx["compiles_in_window"])

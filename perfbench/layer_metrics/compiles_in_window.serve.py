"""Programs compiled between the window's open and its close (persistent-cache
misses, as ``jax.monitoring`` reports them). Expect 0."""


def read(ctx):
    return float(ctx["compiles_in_window"])

"""Device time of one execution of the decode-step program, mean over the
traced part of the window (``XLA Modules`` events of the device's plane)."""


def read(ctx):
    runs = ctx["trace"].module_runs(ctx["config"]["perfbench"]["programs"]["decode_step"])
    if not runs:
        return None
    return 1e3 * sum(e - s for s, e in runs) / len(runs)

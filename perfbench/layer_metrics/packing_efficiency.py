"""Non-padding tokens over rows times row length, counted by the harness on the
batches it fed in the window."""


def read(ctx):
    fed = ctx["fed"]
    if not fed["slots"]:
        return None
    return 100.0 * fed["tokens"] / fed["slots"]

"""The decode step's share of the chip's bf16 peak: the FLOPs the rows that
decoded in the traced interval needed (the family's ``decode_flops`` over their
live lengths, from the client's stamps) over the decode-step programs' device time
there times the peak."""


def read(ctx):
    runs = ctx["trace"].module_runs(ctx["config"]["perfbench"]["programs"]["decode_step"])
    lo, hi = ctx["trace_interval"]
    rows = [live for _, live in ctx["window_tokens"](ctx["load"], lo, hi)]
    seconds = sum(e - s for s, e in runs)
    if seconds <= 0 or not rows:
        return None
    flops = ctx["family"].decode_flops(ctx["config"], rows)
    return 100.0 * flops / (seconds * ctx["peaks"]["bf16_flops_per_s"])

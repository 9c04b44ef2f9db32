"""Paged decode attention's share of its roofline, which memory bounds: the
bytes the rows that decoded in the traced interval had to move (live K and V,
query, output: the family's ``decode_attention_bytes``) over the HBM peak, divided by
the device time of the attention kernel inside the decode-step programs.

The kernel is found by name among the device's operations (the configuration's
``kernels.paged_attention`` patterns; today the Mosaic custom call). An
implementation the trace does not name that way leaves this metric silent.
"""


def read(ctx):
    settings = ctx["config"]["perfbench"]
    trace = ctx["trace"]
    runs = trace.module_runs(settings["programs"]["decode_step"])
    seconds = trace.op_seconds_within(settings["kernels"]["paged_attention"], runs)
    lo, hi = ctx["trace_interval"]
    rows = [live for _, live in ctx["window_tokens"](ctx["load"], lo, hi)]
    if seconds <= 0 or not rows:
        return None
    moved = ctx["family"].decode_attention_bytes(
        ctx["config"], rows, settings["kv_bytes"], settings["act_bytes"]
    )
    return 100.0 * (moved / ctx["peaks"]["hbm_bytes_per_s"]) / seconds

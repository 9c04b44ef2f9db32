"""The window layers' decode attention's share of its roofline, which memory
bounds: the bytes the rows that decoded in the traced interval had to move in
those layers (the last ``sliding_window`` keys and values of each, or all it
has, query in, output out: the family's ``decode_window_bytes``) over the HBM
peak, divided by the device time of the ring's attention calls inside the
decode-step programs.

The calls are found by name among the device's operations (the configuration's
``kernels.window_attention`` patterns; today the Mosaic custom call whose first
operand is the rotated ring table). Silent on a configuration without the
names, a family without the count, and a trace without such a call."""


def read(ctx):
    settings = ctx["config"]["perfbench"]
    names = settings.get("kernels", {}).get("window_attention")
    count = getattr(ctx["family"], "decode_window_bytes", None) if names else None
    trace = ctx["trace"]
    if count is None or trace is None:
        return None
    runs = trace.module_runs(settings["programs"]["decode_step"])
    seconds = trace.op_seconds_within(names, runs)
    lo, hi = ctx["trace_interval"]
    rows = [live for _, live in ctx["window_tokens"](ctx["load"], lo, hi)]
    if seconds <= 0 or not rows:
        return None
    moved = count(ctx["config"], rows, settings["kv_bytes"], settings["act_bytes"])
    return 100.0 * (moved / ctx["peaks"]["hbm_bytes_per_s"]) / seconds

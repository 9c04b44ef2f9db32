"""The routed experts' share of their roofline in decode, which memory bounds
(two rows an expert): the bytes the experts that got a row had to move (their
three projections once, each routed row in and out: the family's
``decode_expert_bytes`` of the window's mean ``experts_hit`` and
``expert_rows`` a dispatched step, from ``/stats`` at the window's open and
close) times the decode-step executions in the traced interval, over the HBM
peak, divided by the device time of the routed-expert operations inside those
executions.

The operations are found by the start of their short name
(``trace.short_op_name``) among the configuration's ``kernels.routed_experts``
names: the grouped products are Mosaic calls like the attention kernel, and a
substring of the whole instruction would also find whatever takes their result
as an operand. Silent on a program without the counters or a configuration
without the names.
"""

from perfbench import trace as traces


def read(ctx):
    settings = ctx["config"]["perfbench"]
    names = tuple(settings.get("kernels", {}).get("routed_experts", ()))
    before = ctx["load"]["stats_open"]["generation"]["pipeline"]
    after = ctx["load"]["stats_close"]["generation"]["pipeline"]
    if not names or "expert_rows" not in after or "expert_rows" not in before:
        return None
    steps = after["step_dispatches"] - before["step_dispatches"]
    trace = ctx["trace"]
    runs = sorted(trace.module_runs(settings["programs"]["decode_step"]))
    if steps <= 0 or not runs or not trace.devices:
        return None
    seconds, i = 0.0, 0
    for name, start, end in sorted(trace.devices[0].ops, key=lambda op: op[1]):
        if not traces.short_op_name(name).startswith(names):
            continue
        while i < len(runs) and runs[i][1] <= start:
            i += 1
        if i < len(runs) and runs[i][0] <= start:
            seconds += end - start
    if seconds <= 0:
        return None
    a_step = ctx["family"].decode_expert_bytes(
        ctx["config"],
        (after["experts_hit"] - before["experts_hit"]) / steps,
        (after["expert_rows"] - before["expert_rows"]) / steps,
        settings["weights_bytes"], settings["act_bytes"],
    )
    return 100.0 * (a_step * len(runs) / ctx["peaks"]["hbm_bytes_per_s"]) / seconds

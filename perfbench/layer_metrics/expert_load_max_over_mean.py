"""How uneven the routing was in decode: the busiest expert's rows over the
mean expert's, summed over the window's decode steps and expert layers (the
family's ``expert_load_max_over_mean`` of ``/stats``' ``expert_rows_max`` and
``expert_rows`` between the window's open and its close). 1 is perfect
balance; at two rows an expert a few is what chance gives. Silent on a program
without the counters."""


def read(ctx):
    before = ctx["load"]["stats_open"]["generation"]["pipeline"]
    after = ctx["load"]["stats_close"]["generation"]["pipeline"]
    if "expert_rows" not in after or "expert_rows" not in before:
        return None
    rows = after["expert_rows"] - before["expert_rows"]
    if rows <= 0:
        return None
    return ctx["family"].expert_load_max_over_mean(
        ctx["config"], after["expert_rows_max"] - before["expert_rows_max"], rows
    )

"""Share of the device's busy time in the traced interval that went to the
prefill programs (bucketed prefill, the paged insert into the pool, chunks):
what admission takes from decoding."""


def read(ctx):
    trace = ctx["trace"]
    busy = trace.busy_s()
    runs = trace.module_runs(ctx["config"]["perfbench"]["programs"]["prefill"])
    if busy <= 0 or not runs:
        return None
    return 100.0 * sum(e - s for s, e in runs) / busy

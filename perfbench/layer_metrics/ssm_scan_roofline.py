"""The prefill recurrence's share of its roofline by bytes: what the scans of
the prompts prefilled in the traced interval had to move (each token's step,
input, ``B`` and ``C`` in, its output out, a prompt's state in and out: the
family's ``prefill_scan_bytes`` of the prompts whose first token came in the
interval) over the HBM peak, divided by the device time of the scan kernel
inside the prefill programs. The scan is elementwise work a token after token
(an exponential and six products and sums a state element): the vector unit
and not memory holds it back, which is what a low share here says.

The operations are found by the start of their short name
(``trace.short_op_name``) among the configuration's ``kernels.ssm_scan`` names:
the Mosaic call is named by its scope. Silent on a configuration without the
names, a family without the count, and a trace without such an operation (XLA's
loop, whose steps are fusions)."""

from perfbench import opnames


def read(ctx):
    settings = ctx["config"]["perfbench"]
    names = settings.get("kernels", {}).get("ssm_scan")
    count = getattr(ctx["family"], "prefill_scan_bytes", None) if names else None
    trace = ctx["trace"]
    if count is None or trace is None:
        return None
    runs = trace.module_runs(settings["programs"]["prefill"])
    seconds = opnames.seconds_within(trace, names, runs)
    lo, hi = ctx["trace_interval"]
    prompts = [
        record["prompt_len"] for record in ctx["load"]["records"]
        if record["token_times"] and lo <= record["token_times"][0] < hi
    ]
    if seconds <= 0 or not prompts:
        return None
    moved = count(ctx["config"], prompts, settings["act_bytes"])
    return 100.0 * (moved / ctx["peaks"]["hbm_bytes_per_s"]) / seconds

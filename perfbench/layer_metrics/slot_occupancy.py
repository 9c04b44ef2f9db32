"""Share of decode rows that produced a token: tokens decoded over step
dispatches times slots, both as ``/stats`` counted them between the window's
open and its close."""


def read(ctx):
    before = ctx["load"]["stats_open"]["generation"]
    after = ctx["load"]["stats_close"]["generation"]
    steps = after["pipeline"]["step_dispatches"] - before["pipeline"]["step_dispatches"]
    tokens = after["tokens_decoded"] - before["tokens_decoded"]
    if steps <= 0:
        return None
    return 100.0 * tokens / (steps * after["num_slots"])

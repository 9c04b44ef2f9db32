"""Bytes the engine keeps resident for a live token: ``/stats``'
``resident_byte_steps`` (per-slot state and ring of every active slot and its
live blocks under the table, summed over dispatched steps) over
``live_token_steps``, between the window's open and its close. A cache of keys
and values for every layer grows by a row a token a layer; layers that keep a
fixed state, a ring, or another layer's keys do not. Silent on a program
without the counters."""


def read(ctx):
    before = ctx["load"]["stats_open"]["generation"]["pipeline"]
    after = ctx["load"]["stats_close"]["generation"]["pipeline"]
    if "resident_byte_steps" not in before or "resident_byte_steps" not in after:
        return None
    tokens = after["live_token_steps"] - before["live_token_steps"]
    if tokens <= 0:
        return None
    return (after["resident_byte_steps"] - before["resident_byte_steps"]) / tokens

"""The generator: the same seed gives the same work, another seed the same set
of sizes in another order; open-loop latency is timed from the due time."""

import numpy as np
import pytest

from perfbench import manifest, serve, traffic

MIXES = sorted((manifest.ROOT / "perfbench" / "traffic").glob("*.json"))
#: an open loop over the closed mix's sizes: 43 arrivals in the ramp, 86 in a 45 s window
OPEN = {"arrival": {"mode": "open", "rate_per_s": 1.92, "ramp_s": 22.4}, "pool": 43}


@pytest.mark.parametrize("path", MIXES + [OPEN], ids=lambda p: getattr(p, "stem", "open"))
def test_same_seed_same_work_other_seed_other_order(path):
    mix = {**traffic.load_mix(MIXES[0]), **path} if isinstance(path, dict) else traffic.load_mix(path)
    if mix["kind"] == "documents":
        a = traffic.documents(mix, 7, 50257, 600)
        b = traffic.documents(mix, 7, 50257, 600)
        c = traffic.documents(mix, 2**31 + 11, 50257, 600)
        assert all(np.array_equal(x, y) for x, y in zip(a, b))
        assert [len(x) for x in a] != [len(x) for x in c]
        assert sorted(len(x) for x in a[:512]) == sorted(len(x) for x in c[:512])
        return
    one, same, other = (traffic.RequestStream(mix, s, 50257) for s in (7, 7, 2**31 + 11))
    n = int(mix["pool"])
    assert [one.request(i) for i in range(20)] == [same.request(i) for i in range(20)]
    assert [one.sizes(i) for i in range(n)] != [other.sizes(i) for i in range(n)]
    assert sorted(one.sizes(i) for i in range(n)) == sorted(other.sizes(i) for i in range(n))
    assert one.prompt(3) != other.prompt(3)
    prompts = [one.sizes(i)[0] for i in range(n)]
    lo, hi = mix["prompt_tokens"]["min"], mix["prompt_tokens"]["max"]
    assert min(prompts) >= lo and max(prompts) <= hi
    assert abs(np.median(prompts) - mix["prompt_tokens"]["median"]) <= 2
    if mix["arrival"]["mode"] == "open":
        rate, ramp = mix["arrival"]["rate_per_s"], mix["arrival"]["ramp_s"]
        due, again = (traffic.arrival_times(mix, s, 45.0) for s in (7, 7))
        shuffled = traffic.arrival_times(mix, 8, 45.0)
        assert np.array_equal(due, again) and not np.array_equal(due, shuffled)
        # the same number in the ramp and in the window on every seed, none outside
        for times in (due, shuffled):
            assert np.sum(times < ramp) == round(rate * ramp)
            assert np.sum((times >= ramp) & (times < ramp + 45.0)) == round(rate * 45.0)
            assert len(times) == round(rate * ramp) + round(rate * 45.0)
            assert np.all(np.diff(times) >= 0)
        window = lambda t: np.sort(np.diff(t[t >= ramp], append=ramp + 45.0))
        assert np.allclose(window(due), window(shuffled))


def test_bursts_keep_the_count_and_crowd_the_arrivals():
    mix = {"arrival": {"mode": "open", "rate_per_s": 4.0, "ramp_s": 0.0,
                       "burst": {"every_s": 15.0, "len_s": 5.0, "factor": 3.0}}}
    times = traffic.arrival_times(mix, 3, 45.0)
    assert len(times) == 180 and times.min() >= 0 and times.max() < 45.0
    inside = np.sum((times % 15.0) < 5.0)
    # a third of the time at three times the rate: 3/5 of the arrivals
    assert abs(inside - 0.6 * 180) <= 6


def test_first_turn_cuts_are_spread_uniformly():
    ranks = traffic.client_ranks(5, 48)
    cuts = sorted(traffic.first_turn_cut(int(r), 48, 48) for r in ranks)
    assert cuts == list(range(1, 49))


def test_percentile_is_nearest_rank():
    values = list(range(1, 101))
    assert traffic.percentile(values, 95) == 95 and traffic.percentile(values, 50) == 50
    assert traffic.percentile([3.0], 90) == 3.0
    with pytest.raises(ValueError):
        traffic.percentile([], 50)


def _record(**kw):
    base = {"index": 0, "prompt_len": 10, "asked": 3, "sent": None, "status": 200,
            "token_times": [], "tokens": [], "done": False, "error": None}
    base.update(kw)
    return base


def test_open_loop_latency_counts_from_the_due_time():
    mix = {"arrival": {"mode": "open"}}
    result = {"t_open": 100.0, "t_close": 110.0, "records": [
        # sent 0.4 s late: the wait counts; first token 1.0 s after it was due
        _record(due=101.0, sent=101.4, token_times=[102.0, 102.5, 103.5], tokens=[1, 2, 3], done=True),
        # due before the window opened: served, not counted
        _record(due=99.0, sent=99.0, token_times=[100.5, 101.0], tokens=[1, 2]),
        # due in the window, never answered: counts as the worst
        _record(due=109.0, sent=109.0),
    ]}
    out = serve.end_to_end(result, mix, grace=60.0)
    assert out["attempted"] == 2 and out["failed"] == 1
    assert out["ttft_p90_ms"] == pytest.approx((110.0 + 60.0 - 109.0) * 1e3)
    assert sorted(out["late_ms"]) == pytest.approx([0.0, 400.0])
    # gaps of every request whose later token fell in the window, tail over all of them
    assert out["itl_samples"] == 3 and out["itl_p95_ms"] == pytest.approx(1000.0)
    assert out["serve_tokens_per_s"] == pytest.approx(5 / 10.0)


def test_closed_loop_counts_tokens_in_the_window_only():
    mix = {"arrival": {"mode": "closed"}}
    result = {"t_open": 10.0, "t_close": 20.0, "records": [
        _record(client=0, sent=5.0, token_times=[9.0, 11.0, 19.0, 21.0], tokens=[1, 2, 3, 4]),
        _record(client=0, sent=19.5, error="dropped_at_close"),
        _record(client=1, sent=2.0, token_times=[3.0, 4.0], tokens=[1, 2], done=True),
    ]}
    out = serve.end_to_end(result, mix)
    assert out["serve_tokens_per_s"] == pytest.approx(2 / 10.0)
    assert out["attempted"] == 2 and out["failed"] == 0
    live = sorted(l for _, l in serve.window_tokens(result))
    assert live == [12, 13]  # prompt 10, second and third token of the answer


"""The reduction from a profiler trace to device times: on hand-made events,
and on the start of two traces recorded on a TPU v5e and kept beside this file
(``recorded/*.json``, written by ``perfbench.trace.dump``)."""

from pathlib import Path

import pytest

from perfbench import trace

RECORDED = Path(__file__).parent / "recorded"


def summary():
    device = trace.DeviceTrace(
        "/device:TPU:0",
        modules=[("jit__multi(1)", 0.0, 1.0), ("jit__prefill(2)", 1.5, 1.7), ("jit__multi(1)", 2.0, 3.0)],
        ops=[("%layer_1.2 = bf16[4]{0} custom-call(bf16[4]{0} %x), custom_call_target=\"tpu_custom_call\"", 0.1, 0.5),
             ("%fusion.7 = bf16[4]{0} fusion(bf16[4]{0} %x), kind=kLoop", 0.5, 1.0),
             ("%fusion.9 = bf16[4]{0} fusion(bf16[4]{0} %x), kind=kLoop", 1.5, 1.7),
             ("%layer_2.2 = bf16[4]{0} custom-call(bf16[4]{0} %x), custom_call_target=\"tpu_custom_call\"", 2.0, 2.9)],
    )
    host = [("python3", "np.asarray(jax.Array)", 0.9, 1.6), ("engine", "admit", 1.7, 2.0)]
    return trace.TraceSummary([device], host, (0.0, 4.0))


def test_union_and_gaps():
    assert trace.union_length([(0, 2), (1, 3), (5, 6)]) == 4
    assert trace.gaps_between([(1, 2), (3, 4)], (0, 5)) == [(0, 1), (2, 3), (4, 5)]
    assert trace.gaps_between([], (0, 1)) == [(0, 1)]


def test_busy_idle_and_program_times():
    s = summary()
    assert s.busy_s() == pytest.approx(0.9 + 0.2 + 0.9)
    assert s.idle_share() == pytest.approx(100 * (1 - 2.0 / 4.0))
    assert s.module_runs(["_multi"]) == [(0.0, 1.0), (2.0, 3.0)]
    assert s.module_runs(["_prefill", "_paged_insert"]) == [(1.5, 1.7)]
    # the kernel inside the decode steps only, by its custom-call target
    assert s.op_seconds_within(["tpu_custom_call"], s.module_runs(["_multi"])) == pytest.approx(1.3)
    assert s.op_seconds_within(["tpu_custom_call"], s.module_runs(["_prefill"])) == 0.0


def test_breakdown_names_operations_and_gaps():
    s = summary()
    top = dict(s.top_ops(10))
    assert top["layer_ custom-call tpu_custom_call"] == pytest.approx(1.3)
    assert top["fusion fusion"] == pytest.approx(0.7)
    gaps = s.idle_gaps(10)
    assert gaps[0][1] == pytest.approx(1.1) and gaps[0][0] == "no host event"  # 2.9 .. 4.0
    assert ["python3: np.asarray(jax.Array)", pytest.approx(0.5)] in gaps  # 1.0 .. 1.5
    assert ["engine: admit", pytest.approx(0.3)] in gaps


def test_short_op_names():
    assert trace.short_op_name(
        '%copy.388 = bf16[3073,16,16,64]{3,1,2,0:T(8,128)(2,1)} copy(bf16[3073,16,16,64]{0,3,2,1} %pool__layer_15____v__.1)'
    ) == "copy copy"
    assert trace.short_op_name("%fusion.12 = (f32[8]{0}, bf16[3]{0}) fusion(f32[2] %a), kind=kLoop") == "fusion fusion"
    assert trace.short_op_name("jit__multi(123)") == "jit__multi(123)"


def test_dump_and_load_round_trip(tmp_path):
    s = summary()
    trace.dump(s, str(tmp_path / "t.json"), module_runs=2)
    back = trace.load(str(tmp_path / "t.json"))
    assert back.module_runs(["_multi"]) == [(0.0, 1.0)] and back.window == (0.0, 1.7)
    assert [name for name, _, _ in back.devices[0].ops] == [
        "layer_ custom-call tpu_custom_call", "fusion fusion", "fusion fusion"]


def test_reading_a_profile_made_here(tmp_path):
    """``read`` on a real ``.xplane.pb``: the CPU backend has no device plane,
    so the summary holds host events only and every device reading is silent."""
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    jnp.ones((64, 64)).dot(jnp.ones((64, 64))).block_until_ready()
    jax.profiler.stop_trace()
    s = trace.read(str(tmp_path))
    assert s.devices == [] and s.busy_s() == 0.0 and s.idle_share() is None
    assert s.window_s > 0 and s.module_runs(["jit"]) == [] and s.top_ops() == []
    with pytest.raises(FileNotFoundError):
        trace.find_xplane(str(tmp_path / "nothing"))


@pytest.mark.parametrize("name,program,kernel", [
    ("closed", "_multi", "tpu_custom_call"), ("train", "train_step", None)])
def test_recorded_trace(name, program, kernel):
    s = trace.load(str(RECORDED / f"trace_{name}.json"))
    assert [d.name for d in s.devices] == ["/device:TPU:0"]
    runs = s.module_runs([program])
    assert len(runs) >= 2 and all(0.01 < e - b < 2.0 for b, e in runs)
    assert 0 < s.busy_s() <= s.window_s and 0 <= s.idle_share() < 100
    inside = s.op_seconds_within([""], runs)
    assert inside <= sum(e - b for b, e in runs) * 1.0001
    assert inside > 0.5 * sum(e - b for b, e in runs)  # operations fill their programs
    if kernel:
        share = s.op_seconds_within([kernel], runs) / sum(e - b for b, e in runs)
        assert 0.05 < share <= 1.0
    assert len(s.top_ops(10)) == 10 and s.top_ops(10)[0][1] > 0

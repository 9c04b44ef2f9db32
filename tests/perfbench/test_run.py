"""One run of each kind of cell, end to end, at a toy size on the CPU.

``run.execute`` is everything of a run but the look for a chip. The toy cells
state float32, so the control of their comparison is the reference in
bfloat16; the limits in ``tiny.py`` lie between the program's readings
(round-off, under 1e-5) and the control's (over 1e-3).
"""

import dataclasses
import json
import shutil
import subprocess
import sys

import pytest

from perfbench import manifest
from tests.perfbench import tiny

REQUIRED = ["correct", "attempted", "failed", "metrics", "device"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("bench"))


execute = tiny.execute


def numbers(result):
    return {k: v["value"] for k, v in result["compared"].items()}, \
        {k: v["limit"] for k, v in result["compared"].items()}


def test_closed_cell_line_and_its_control(root):
    result = execute(root, "tiny.closed", control=1)
    keys = list(json.loads(json.dumps(result)))
    assert keys[: len(REQUIRED)] == REQUIRED and keys[-1] == "compared"
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    for metric in result["metrics"].values():
        assert set(metric) == {"value", "unit"} and metric["value"] > 0
    assert set(result["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    value, limit = numbers(result)
    assert value["logit_gap"] <= limit["logit_gap"] < value["control_bf16_logit_gap"]
    assert value["tokens_compared"] >= 20 and value["short_answers"] == 0


def test_traced_run_reports_per_layer_metrics_only(root):
    result = execute(root, "tiny.closed", trace=1)
    cell = tiny.cell(root, "tiny.closed")
    assert set(result["metrics"]) <= {m["name"] for m in cell.per_layer()}
    # counters are there on any backend; device-trace metrics stay silent off the chip
    assert {"slot_occupancy", "itl_tail_mean_ms", "itl_p95_ms", "compiles_in_window.serve"} <= set(result["metrics"])
    assert result["metrics"]["compiles_in_window.serve"]["value"] == 0
    assert "paged_attn_roofline" not in result["metrics"]
    assert {"busy_s", "window_s"} <= set(result["device"])
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    assert result["correct"] is True


def test_open_cell_times_first_tokens_from_the_due_time(root):
    result = execute(root, "tiny.open", seconds=1.5)
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"itl_tail_mean_ms", "ttft_p90_ms", "setup_s"}
    # ten requests a second for 1.5 s, the same count on every seed
    assert result["attempted"] == 15


def test_traced_open_cell_reads_the_generator_and_the_queue(root):
    result = execute(root, "tiny.open", trace=1, seconds=1.5)
    assert result["correct"] is True
    # host-side readers answer on any backend; the device's share of prefill needs a chip
    assert {"gen_late_p95_ms", "queue_wait_p95_ms"} <= set(result["metrics"])
    assert result["metrics"]["queue_wait_p95_ms"]["value"] >= 0
    assert "prefill_time_share" not in result["metrics"]


def test_altered_token_is_not_correct(root, monkeypatch):
    from unionml_tpu.serving.continuous import DecodeEngine

    step = DecodeEngine.step

    def altered(self, *a, **kw):
        events = list(step(self, *a, **kw))
        for i, event in enumerate(events):
            if event.emit and event.error is None:
                wrong = (event.token + 1) % self._config.vocab_size
                events[i] = dataclasses.replace(event, token=wrong)
                break
        return events

    monkeypatch.setattr(DecodeEngine, "step", altered)
    result = execute(root, "tiny.closed")
    value, limit = numbers(result)
    assert result["correct"] is False and value["logit_gap"] > limit["logit_gap"]


def test_train_cell_line_and_its_control(root):
    result = execute(root, "tiny.train", control=1)
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 3
    assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}
    value, limit = numbers(result)
    for name in ("loss1_gap", "loss2_gap", "loss3_gap", "grad_norm_gap", "delta_norm_gap"):
        assert value[name] <= limit[name]
    # the control fails one of the cell's numbers, and so does half a batch
    assert any(value[f"control_bf16_{k}"] > limit[k] for k in limit if limit[k] is not None)
    assert value["halfbatch_grad_norm_gap"] > 10 * limit["grad_norm_gap"]


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_broken_train_step_is_not_correct(root, fault):
    result = execute(root, "tiny.train", fault=fault)
    assert result["correct"] is False
    value, limit = numbers(result)
    failing = [k for k in limit if limit[k] is not None and not value[k] <= limit[k]]
    assert failing, value
    if fault == "state_unchanged":  # nothing moved: the change reads 1 against the reference's
        assert value["delta_norm_gap"] == pytest.approx(1.0, abs=1e-3)


def _run_command(cwd, env_extra):
    import os

    bench = manifest.load()
    command = [sys.executable if w == "python3" else w for w in bench["command"]]
    command += ["--workload", bench["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
                "--trace", "0"]
    env = {**os.environ, **env_extra}
    return subprocess.run(command, cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_accelerator_no_result():
    done = _run_command(manifest.ROOT, {"JAX_PLATFORMS": "cpu"})
    assert done.returncode != 0 and done.stdout.strip() == ""
    assert "needs a TPU" in done.stderr


def test_benchmark_alone_is_no_benchmark(tmp_path):
    """In a directory with ``BENCHMARK.json`` and the files under ``paths``
    only, the command finds no program to measure: no result, no zero."""
    bench = manifest.load()
    shutil.copy(manifest.ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for base in bench["paths"]:
        shutil.copytree(manifest.ROOT / base, tmp_path / base,
                        ignore=shutil.ignore_patterns("__pycache__"))
    probe = ("import sys; sys.path.insert(0, '.'); import perfbench.run, importlib.util; "
             "sys.exit(0 if importlib.util.find_spec('unionml_tpu') is None else 3)")
    assert subprocess.run([sys.executable, "-c", probe], cwd=tmp_path, timeout=120).returncode == 0
    done = _run_command(tmp_path, {"JAX_PLATFORMS": "cpu"})
    assert done.returncode != 0 and done.stdout.strip() == ""

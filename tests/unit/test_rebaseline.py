"""tools/rebaseline.py guardrails: the bench-baseline ratchet must be safe unattended.

The tool runs only after a successful on-TPU bench run, so every
branch is pinned here on CPU against a temp copy of bench.py: wrong-metric and
CPU results refused, out-of-band values refused, within-2%/downward kept, real
improvements rewritten atomically with mode preserved.
"""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]


@pytest.fixture
def workdir(tmp_path):
    """A minimal repo copy the tool can rewrite: tools/rebaseline.py + bench.py."""
    (tmp_path / "tools").mkdir()
    shutil.copy(REPO / "tools" / "rebaseline.py", tmp_path / "tools" / "rebaseline.py")
    shutil.copy(REPO / "bench.py", tmp_path / "bench.py")
    os.chmod(tmp_path / "bench.py", 0o644)
    return tmp_path


def run_tool(workdir, payload) -> subprocess.CompletedProcess:
    out = workdir / "bench.out"
    out.write_text(payload if isinstance(payload, str) else json.dumps(payload))
    return subprocess.run(
        [sys.executable, str(workdir / "tools" / "rebaseline.py"), str(out)],
        capture_output=True,
        text=True,
    )


def baseline_of(workdir) -> float:
    for line in (workdir / "bench.py").read_text().splitlines():
        if line.startswith("BASELINE_EXAMPLES_PER_S = "):
            return float(line.split("=")[1])
    raise AssertionError("constant missing")


def test_refuses_cpu_and_foreign_results(workdir):
    before = baseline_of(workdir)
    # no mfu field = not an accelerator run
    assert run_tool(workdir, {"metric": "bert_base_finetune_throughput", "value": 5000.0}).returncode == 1
    # wrong metric entirely
    assert run_tool(workdir, {"metric": "other", "value": 5000.0, "mfu": 0.4}).returncode == 1
    # valid JSON, wrong type
    assert run_tool(workdir, "[1, 2]").returncode == 1
    # null / non-numeric value fields refuse cleanly, no traceback
    for bad in (None, "n/a"):
        proc = run_tool(workdir, {"metric": "bert_base_finetune_throughput", "value": bad, "mfu": 0.3})
        assert proc.returncode == 1 and "Traceback" not in proc.stderr, proc.stderr
    # unreadable / non-JSON
    assert run_tool(workdir, "not json at all").returncode == 1
    assert baseline_of(workdir) == before


def test_refuses_out_of_band_values(workdir):
    before = baseline_of(workdir)
    for value in (0.0, 50.0, 1e6):
        proc = run_tool(workdir, {"metric": "bert_base_finetune_throughput", "value": value, "mfu": 0.3})
        assert proc.returncode == 1, proc.stderr
    assert baseline_of(workdir) == before


def test_keeps_baseline_for_small_or_downward_moves(workdir):
    before = baseline_of(workdir)
    for value in (before * 0.9, before, before * 1.019):
        proc = run_tool(workdir, {"metric": "bert_base_finetune_throughput", "value": value, "mfu": 0.3})
        assert proc.returncode == 0, proc.stderr  # a kept baseline is success
    assert baseline_of(workdir) == before


def test_ratchets_upward_and_preserves_file_integrity(workdir):
    import ast

    before = baseline_of(workdir)
    target = round(before * 1.5, 1)  # comfortably beyond the 2% band, inside the sane band
    proc = run_tool(workdir, {"metric": "bert_base_finetune_throughput", "value": target, "mfu": 0.37})
    assert proc.returncode == 0, proc.stderr
    assert baseline_of(workdir) == target
    bench = workdir / "bench.py"
    ast.parse(bench.read_text())  # still valid python
    assert (os.stat(bench).st_mode & 0o777) == 0o644  # mode preserved through the swap
    assert not list(workdir.glob(".bench.py.*"))  # no stray temp files
    assert f"rebaseline: BASELINE_EXAMPLES_PER_S {before:.1f} -> {target:.1f}" in proc.stderr
    # the ratchet composes: a second, slower "window" keeps the new baseline
    proc = run_tool(workdir, {"metric": "bert_base_finetune_throughput", "value": before, "mfu": 0.3})
    assert proc.returncode == 0
    assert baseline_of(workdir) == target

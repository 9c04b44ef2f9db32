"""An architecture reaches the harness through its family's adapter and its
reference alone: the ``gpt2`` family draws the weights it always drew, a family
the harness has never heard of (``toybench/``) runs both kinds of cell with its
own counts in the readings, and no harness file names an architecture."""

import hashlib
import os
from pathlib import Path

import jax
import numpy as np
import pytest

from perfbench import manifest, peaks, trace
from tests.perfbench import tiny

RECORDED = Path(__file__).parent / "recorded"
LEVEL_0 = "xla_backend_optimization_level=0" in os.environ.get("XLA_FLAGS", "")  # conftest.py asks for it

#: SHA-256 over the leaves in path order of the parent's ``weights.make_params``
#: (commit 5359724, before the family existed), on the CPU. XLA's optimisation
#: level moves float32's last bits, so the float32 case has a digest for each.
PARENT_DIGESTS = {
    ("gpt2's own init", 7, "float32"): {
        True: "a9c4b5b8d7ba253417eb8bd18b3d88c746f8b5bc29962215cd2580138ab05e1b",
        False: "d37c62b8ac572813d6cd0dfe40c9ffa2f2a6507515239acf2b7b2efc725def46",
    }[LEVEL_0],
    ("the serving recipe", 2**31 + 5, "bfloat16"):
        "3efb271a7d4f2941c2263e64d690893563c68e89c5d11d4b72af0eecac7bceb9",
}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("bench"))


def digest(tree):
    sha = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        sha.update(jax.tree_util.keystr(path).encode())
        sha.update(np.asarray(leaf).tobytes())
    return sha.hexdigest()


@pytest.mark.parametrize("case", list(PARENT_DIGESTS), ids=lambda case: case[0])
def test_same_seed_same_weights_as_before_the_family(case):
    recipe, seed, dtype = case
    config = dict(tiny.TINY_SIZES)
    if recipe == "the serving recipe":
        config["perfbench"] = {"init": tiny.TINY_SERVE_INIT}
    assert digest(tiny.gpt2_family().make_params(config, seed, dtype)) == PARENT_DIGESTS[case]


def recorded_trace(monkeypatch, name):
    """Have the run read a trace recorded on a TPU v5e in place of the CPU's
    own, which has no device plane: the device-trace readers then answer."""
    def read(log_dir, max_host_events=200_000):
        return trace.load(str(RECORDED / f"trace_{name}.json"))

    monkeypatch.setattr(trace, "read", read)


def spy(monkeypatch, module, name):
    calls, function = [], getattr(module, name)

    def spied(*args):
        calls.append((args, function(*args)))
        return calls[-1][1]

    monkeypatch.setattr(module, name, spied)
    return calls


def test_another_family_is_not_the_gpt2_family(root):
    cell = tiny.cell(root, "toy.closed")
    assert not {"n_embd", "n_head", "n_layer", "n_inner", "n_positions", "layer_norm_epsilon"} & set(cell.config)
    assert cell.family().__file__.endswith("toybench/families/toy.py")
    assert cell.reference().__file__.endswith("toybench/reference/toy.py")
    assert set(cell.family().reference_kwargs(cell.config)) == {"heads", "epsilon"}
    # no file of the harness was written for it
    assert not list((manifest.ROOT / "perfbench").rglob("*toy*"))


@pytest.mark.parametrize("name,metric", [("toy.closed", "serve_tokens_per_s"), ("toy.train", "train_tokens_per_s")])
def test_another_family_runs_correct_and_fails_its_control(root, name, metric):
    result = tiny.execute(root, name, control=1)
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {metric, "setup_s"}
    value = {k: v["value"] for k, v in result["compared"].items()}
    limit = {k: v["limit"] for k, v in result["compared"].items() if v["limit"] is not None}
    assert all(value[k] <= limit[k] for k in limit)
    assert any(value[f"control_bf16_{k}"] > limit[k] for k in limit if f"control_bf16_{k}" in value)


def test_another_familys_decode_readings_are_of_its_own_counts(root, monkeypatch):
    toy, twin = tiny.cell(root, "toy.closed"), tiny.cell(root, "tiny.closed")
    recorded_trace(monkeypatch, "closed")
    flops = spy(monkeypatch, toy.family(), "decode_flops")
    moved = spy(monkeypatch, toy.family(), "decode_attention_bytes")
    result = tiny.execute(root, "toy.closed", trace=1)
    assert result["correct"] is True
    assert {"decode_step_mfu", "paged_attn_roofline", "decode_step_ms", "slot_occupancy"} <= set(result["metrics"])
    ((config, rows), counted), = flops
    assert config == toy.config and len(rows) > 0
    mark = toy.family().COUNT_MARK
    assert counted == pytest.approx(mark * twin.family().decode_flops(twin.config, rows))
    summary = trace.load(str(RECORDED / "trace_closed.json"))
    seconds = sum(e - s for s, e in summary.module_runs(toy.config["perfbench"]["programs"]["decode_step"]))
    peak = peaks.for_device("TPU v5 lite")["bf16_flops_per_s"]
    assert result["metrics"]["decode_step_mfu"]["value"] == pytest.approx(100 * counted / (seconds * peak))
    ((_, rows, kv_bytes, act_bytes), bytes_counted), = moved
    assert (kv_bytes, act_bytes) == (4, 4)
    assert bytes_counted == pytest.approx(mark * twin.family().decode_attention_bytes(twin.config, rows, 4, 4))


def test_another_familys_train_reading_is_of_its_own_count(root, monkeypatch):
    toy, twin = tiny.cell(root, "toy.train"), tiny.cell(root, "tiny.train")
    recorded_trace(monkeypatch, "train")
    per_token = spy(monkeypatch, toy.family(), "train_flops_per_token")
    result = tiny.execute(root, "toy.train", trace=1)
    assert result["correct"] is True
    assert {"train_step_mfu", "train_step_ms", "packing_efficiency"} <= set(result["metrics"])
    ((config, mean_keys), counted), = per_token
    assert config == toy.config and 1 < mean_keys < 128
    assert counted == pytest.approx(
        toy.family().COUNT_MARK * twin.family().train_flops_per_token(twin.config, mean_keys))
    assert result["metrics"]["train_step_mfu"]["value"] > 0


ARCHITECTURE = ("gpt2", "GPTLMHeadModel", "GPTConfig", "program_config", "n_embd", "n_head", "n_layer",
                "n_inner", "n_positions", "layer_norm_epsilon", "resid_pdrop")
HARNESS = ("run", "manifest", "serve", "train", "common", "trace", "traffic", "loadgen", "phases", "peaks",
           "weights", "costs")


def test_no_harness_file_and_no_reader_names_an_architecture():
    """The next edit that wires an architecture back into a driver or a reader
    fails here, on the CPU."""
    files = [manifest.ROOT / "perfbench" / f"{name}.py" for name in HARNESS]
    for base in manifest.load()["paths"]:
        files += sorted((manifest.ROOT / base / "layer_metrics").glob("*.py"))
    assert len(files) > len(HARNESS) + 10
    found = [(path.name, word) for path in files for word in ARCHITECTURE if word in path.read_text()]
    assert not found, found
    # and the program's model is built in a family, nowhere else in the benchmark
    for path in (manifest.ROOT / "perfbench").rglob("*.py"):
        if "unionml_tpu.models.gpt" in path.read_text():
            assert path.parent.name == "families", path

"""``BENCHMARK.json`` keeps to the contract, and every cell's files are found
by the names in it, its architecture's adapter and reference among them; a new
cell arrives as files plus one entry."""

import ast
import json
import re
from pathlib import Path

import pytest

from perfbench import manifest
from tests.perfbench import tiny

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTHS = re.compile(r"(hidden|intermediate|latent|state|proj|_dim$|_rank$|head_size|n_embd|n_inner|expan)")


#: what a cell of each kind asks of its family's adapter, and of its reference
ASKED = {
    "serve": (("model", "make_params", "reference_kwargs", "decode_flops", "decode_attention_bytes"),
              ("logits_at",)),
    "train": (("model", "make_params", "reference_kwargs", "architecture_leaves", "train_flops_per_token"),
              ("loss_and_grads", "adamw_step")),
}


@pytest.fixture(scope="module")
def bench():
    return manifest.load()


def test_top_level_keys_and_sizes(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert len(json.dumps(bench)) < 64 * 1024
    assert isinstance(bench["run_seconds"], int) and 1 <= bench["run_seconds"] <= 51
    # a full check with 24 cells has to fit into 43200 s
    runs = 2 + 14 * 24
    assert runs * (bench["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200
    assert 1 <= len(bench["paths"]) <= 16 and len(bench["command"]) <= 32
    for word in bench["command"]:
        assert not word.startswith("/") and ".." not in word


def test_entries_have_exactly_the_contract_keys(bench):
    for config in bench["configs"]:
        assert set(config) == {"name", "source", "file", "reduced", "why"}
        assert any(config["file"].startswith(p + "/") for p in bench["paths"])
        assert not any(WIDTHS.search(key) for key in config["reduced"]), config["reduced"]
        assert len(config["reduced"]) <= 16
    for cell in bench["workloads"]:
        assert set(cell) == {"name", "config", "traffic", "chips", "why"}
        assert cell["chips"] in (1, 4) and 1 <= len(cell["why"]) <= 200
    for metric in bench["end_to_end"]:
        assert set(metric) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert 0.01 <= metric["bound"] <= 0.1
        assert metric["source"] in ("host_clock", "device_trace")
    for metric in bench["per_layer"]:
        assert set(metric) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert metric["source"] in SOURCES
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    for entry in bench["configs"] + bench["workloads"] + bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(entry["name"]), entry["name"]
    for metric in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(metric["unit"]) and metric["better"] in ("lower", "higher")
    assert "setup_s" in [m["name"] for m in bench["end_to_end"]]
    four = sum(1 for c in bench["workloads"] if c["chips"] == 4)
    assert four <= max(1, len(bench["workloads"]) // 4)


def test_every_cell_finds_its_files_and_reports_enough(bench):
    e2e = {m["name"] for m in bench["end_to_end"]}
    for entry in bench["workloads"]:
        cell = manifest.Cell(bench, entry["name"])
        assert cell.config["perfbench"]["kind"] in ("serve", "train")
        assert cell.config["source"] == cell.config_entry["source"]
        assert cell.config["reduced"] == cell.config_entry["reduced"]
        reported = [m["name"] for m in cell.end_to_end()]
        assert "setup_s" in reported and len(reported) >= 2
        layer = cell.per_layer()
        assert layer, entry["name"]
        for metric in layer:
            assert metric["moves"] in reported, (entry["name"], metric["name"])
            assert callable(cell.reader(metric["name"]))
    for metric in bench["per_layer"]:
        assert metric["moves"] in e2e
        for cell in metric.get("workloads", ()):
            assert cell in [w["name"] for w in bench["workloads"]]
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}


def test_files_under_paths_are_named_from_a_names_characters(bench):
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for base in bench["paths"]:
        for path in (manifest.ROOT / base).rglob("*"):
            if "__pycache__" in path.parts or path.suffix == ".pyc":
                continue
            assert ok.match(str(path.relative_to(manifest.ROOT))), path


def test_a_cell_is_added_by_files_and_one_entry(tmp_path):
    root = tiny.make_root(tmp_path)
    before = {w["name"] for w in manifest.load()["workloads"]}
    cell = tiny.cell(root, "tiny.closed")
    assert cell.name not in before
    assert cell.config["n_embd"] == 64 and cell.mix["arrival"]["clients"] == 2
    assert "serve_tokens_per_s" in [m["name"] for m in cell.end_to_end()]
    # a metric of its own, read by a reader of its own, in the new directory
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"].append({"name": "answer", "unit": "count", "better": "higher",
                               "source": "program_counter", "layer": "batcher and scheduler",
                               "moves": "serve_tokens_per_s", "workloads": ["tiny.closed"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    reader = root / "tinybench" / "layer_metrics" / "answer.py"
    reader.parent.mkdir()
    reader.write_text("def read(ctx):\n    return 42.0\n")
    cell = tiny.cell(root, "tiny.closed")
    assert "answer" in [m["name"] for m in cell.per_layer()]
    assert cell.reader("answer")({}) == 42.0
    with pytest.raises(KeyError):
        tiny.cell(root, "no.such.cell")


@pytest.mark.parametrize("toy", [False, True], ids=["the benchmark", "the tests' toy root"])
def test_every_configuration_names_its_family_and_its_reference(toy, tmp_path):
    root = tiny.make_root(tmp_path) if toy else manifest.ROOT
    bench = manifest.load(root)
    for entry in bench["workloads"]:
        cell = manifest.Cell(bench, entry["name"], root=root)
        settings = cell.config["perfbench"]
        assert isinstance(cell.config["vocab_size"], int), "the load generator is handed it"
        family = cell.family()
        assert Path(family.__file__).name == settings["family"] + ".py"
        of_family, of_reference = ASKED[settings["kind"]]
        for name in of_family:
            assert callable(getattr(family, name)), (settings["family"], name)
        # the reference: a file under paths that brings what the check calls and
        # imports nothing of the program or of a family (read, not run)
        assert any(settings["reference"].startswith(base + "/") for base in bench["paths"])
        tree = ast.parse((manifest.ROOT / settings["reference"]).read_text())
        imported, brought = [], set()
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                imported += [alias.name for alias in node.names]
            elif isinstance(node, ast.ImportFrom):
                imported += [f"{node.module}.{alias.name}" for alias in node.names]
        for node in tree.body:
            if isinstance(node, ast.FunctionDef):
                brought.add(node.name)
            elif isinstance(node, ast.Assign):
                brought |= {target.id for target in node.targets if isinstance(target, ast.Name)}
        assert set(of_reference) <= brought, (settings["reference"], of_reference)
        for name in imported:
            assert name.split(".")[0] != "unionml_tpu" and not name.startswith("perfbench.families"), name


def test_what_is_named_and_not_there_is_an_error_that_says_where(tmp_path):
    root = tiny.make_root(tmp_path)
    cell = tiny.cell(root, "toy.train")
    cell.config["perfbench"]["family"] = "nobody"
    with pytest.raises(FileNotFoundError, match=r"families/nobody\.py is under none of"):
        cell.family()
    cell.config["perfbench"]["reference"] = "unionml_tpu/models/gpt.py"  # a file, but of the program
    with pytest.raises(FileNotFoundError, match=r"unionml_tpu/models/gpt\.py is under none of"):
        cell.reference()
    cell.config["perfbench"]["reference"] = tiny.TOYBENCH + "/reference/nothing.py"
    with pytest.raises(FileNotFoundError, match=r"reference/nothing\.py"):
        cell.reference()
    # a serving family need not bring what training asks: asking is the error
    bare = tmp_path / "bare.py"
    bare.write_text("def logits_at():\n    return 1\n")
    module = manifest.load_module(bare)
    assert module.logits_at() == 1 and not hasattr(module, "loss_and_grads")
    with pytest.raises(AttributeError, match=r"bare\.py brings no 'loss_and_grads'"):
        module.loss_and_grads

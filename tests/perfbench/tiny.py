"""A benchmark root of toy cells for the CPU tests: the real ``BENCHMARK.json``
with five more cells, each brought by files of its own in a directory of its
own — which is how a later PR adds a cell. Three are toy sizes of the real
configurations (``tinybench/``, written here). Two are of another family, the
``toy`` family, whose files lie in ``toybench/`` beside this file as a later PR
would commit them: configurations in another model's key names, the family's
adapter, its reference, traffic and limits; here they get their entries."""

from __future__ import annotations

import copy
import json
import time
from pathlib import Path
from typing import Any, Dict

from perfbench import manifest, run

TINY_SIZES = dict(n_embd=64, n_head=4, n_layer=4, n_positions=128, n_ctx=128, vocab_size=2048)
#: hotter than the real cells' recipe: four toy layers have to amplify rounding as 24 real ones do
TINY_SERVE_INIT = {"kernel_std": None, "residual_std": None, "gain": 1.5, "qk_gain": 3.0}


#: the other family's directory, as ``paths`` names it
TOYBENCH = "tests/perfbench/toybench"

OPEN_LOOP_READERS = (("gen_late_p95_ms", "host_clock", "load generator"),
                     ("queue_wait_p95_ms", "program_span", "batcher and scheduler"),
                     ("prefill_time_share", "device_trace", "compiled step programs"))


def _write(path: Path, obj: Dict[str, Any]) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(obj, indent=1))


def make_root(tmp: Path) -> Path:
    real = manifest.load()
    bench = copy.deepcopy(real)
    bench["paths"] = real["paths"] + ["tinybench", TOYBENCH]
    base = manifest.ROOT / "perfbench"

    serve = json.loads((base / "configs" / "gpt2-medium-serve.json").read_text())
    serve.update(TINY_SIZES)
    serve["perfbench"]["name"] = "tiny-serve"
    serve["perfbench"]["init"] = TINY_SERVE_INIT
    serve["perfbench"].update(compute_dtype="float32", weights_dtype="float32")
    serve["perfbench"]["engine"] = {"num_slots": 2, "max_len": 128, "prefill_buckets": [16], "prefill_batch": 2}
    _write(tmp / "tinybench/configs/tiny-serve.json", serve)

    train = json.loads((base / "configs" / "gpt2-small-lm-train.json").read_text())
    train.update(TINY_SIZES)
    train["perfbench"]["name"] = "tiny-train"
    train["perfbench"]["compute_dtype"] = "float32"
    train["perfbench"]["trainer"].update(rows_per_step=4, seq_len=128)
    _write(tmp / "tinybench/configs/tiny-train.json", train)

    closed = json.loads((base / "traffic" / "decode-closed.json").read_text())
    closed["arrival"]["clients"] = 2
    closed["prompt_tokens"].update(median=10, min=4, max=16)
    closed["output_tokens"].update(median=6, min=3, max=10)
    closed["pool"] = 8
    closed["trace_seconds"] = 0.5
    _write(tmp / "tinybench/traffic/tiny-closed.json", closed)
    opened = dict(closed, arrival={"mode": "open", "rate_per_s": 10.0, "ramp_s": 0.5})
    _write(tmp / "tinybench/traffic/tiny-open.json", opened)
    docs = json.loads((base / "traffic" / "packed-train.json").read_text())
    docs["document_tokens"].update(median=32, min=4, max=128)
    docs.update(pool=64, call_seconds=0.3, trace_seconds=0.3)
    _write(tmp / "tinybench/traffic/tiny-docs.json", docs)

    # the toy cells state float32, so their control is the reference in bfloat16
    serve_limits = {"sample_requests": 6, "reference_pad_to": 32, "logit_gap": 0.002,
                    "controls": ["bf16"]}
    _write(tmp / "tinybench/limits/tiny.closed.json", serve_limits)
    _write(tmp / "tinybench/limits/tiny.open.json", serve_limits)
    _write(tmp / "tinybench/limits/tiny.train.json", {
        "loss1_gap": 1e-3, "loss2_gap": 1e-3, "loss3_gap": 1e-3,
        "grad_norm_gap": 5e-4, "delta_norm_gap": 5e-4, "controls": ["bf16"],
    })

    for name, base, why in (("tiny-serve", "tinybench", "toy serving"), ("tiny-train", "tinybench", "toy training"),
                            ("toy-serve", TOYBENCH, "another family, served"),
                            ("toy-train", TOYBENCH, "another family, trained")):
        source = "https://example.org/" + name.split("-")[0]
        bench["configs"].append({"name": name, "source": source, "file": f"{base}/configs/{name}.json",
                                 "reduced": [], "why": why})
    cells = {"tiny.closed": ("tiny-serve", "tiny-closed", ["serve_tokens_per_s"]),
             "tiny.open": ("tiny-serve", "tiny-open", ["itl_tail_mean_ms", "ttft_p90_ms"]),
             "tiny.train": ("tiny-train", "tiny-docs", ["train_tokens_per_s"]),
             "toy.closed": ("toy-serve", "toy-closed", ["serve_tokens_per_s"]),
             "toy.train": ("toy-train", "toy-docs", ["train_tokens_per_s"])}
    known = {m["name"]: m for m in bench["end_to_end"]}
    for cell, (config, mix, reports) in cells.items():
        bench["workloads"].append({"name": cell, "config": config, "traffic": mix, "chips": 1,
                                   "why": "toy cell for the CPU tests"})
        for name in reports:
            if name not in known:  # a metric whose real cell this benchmark does not hold (yet)
                known[name] = {"name": name, "unit": "ms", "better": "lower", "bound": 0.05,
                               "source": "host_clock", "workloads": []}
                bench["end_to_end"].append(known[name])
            known[name]["workloads"].append(cell)
        for metric in bench["per_layer"]:
            if metric["moves"] in reports and "workloads" in metric:
                metric["workloads"].append(cell)
    # the open loop's readers are in the harness; the real benchmark has no open cell (yet) to list them
    listed = {m["name"] for m in bench["per_layer"]}
    for name, source, layer in OPEN_LOOP_READERS:
        if name not in listed:
            bench["per_layer"].append({"name": name, "unit": "ms", "better": "lower", "source": source,
                                       "layer": layer, "moves": "ttft_p90_ms", "workloads": ["tiny.open"]})
    _write(tmp / "BENCHMARK.json", bench)
    return tmp


def cell(root: Path, name: str) -> manifest.Cell:
    return manifest.Cell(manifest.load(root), name, root=root)


def gpt2_family():
    return manifest.load_module(manifest.ROOT / "perfbench" / "families" / "gpt2.py")


def execute(root: Path, name: str, trace=0, control=0, fault=None, seconds=1.0, seed=2**31 + 5) -> Dict[str, Any]:
    """Everything of a run but the look for a chip, on a toy cell."""
    args = run.parse(["--workload", name, "--seed", str(seed), "--seconds", str(seconds),
                      "--trace", str(trace), "--control", str(control)])
    args.fault = fault
    return run.execute(cell(root, name), args, time.perf_counter(), peaks_for="TPU v5 lite")

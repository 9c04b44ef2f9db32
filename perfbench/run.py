"""One cell, one run: ``python3 perfbench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>``.

Looks the cell up in ``BENCHMARK.json``, requires a TPU with the chips the cell
asks for, hands the cell to the driver its configuration names
(``perfbench.<kind>`` for ``"kind": "serve"`` or ``"train"``), and prints one
JSON line: with ``--trace 0`` the cell's end-to-end metrics, with ``--trace 1``
its per-layer metrics, each read by ``layer_metrics/<name>.py``. ``--control 1``
also reads the low-precision control's numbers (never in the driver's runs).
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))


def parse(argv: Optional[List[str]] = None) -> argparse.Namespace:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--control", type=int, choices=(0, 1), default=0)
    return parser.parse_args(argv)


def require_tpu(chips: int) -> None:
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        raise SystemExit(f"perfbench: needs a TPU, but jax.default_backend() is {backend!r}")
    if len(jax.devices()) < chips:
        raise SystemExit(f"perfbench: the cell asks for {chips} chips, JAX sees {len(jax.devices())}")


def execute(cell, args: argparse.Namespace, t0: float, peaks_for: Optional[str] = None) -> Dict[str, Any]:
    """Drive the cell and build the result line's object."""
    from perfbench import common, peaks

    kind = cell.config["perfbench"]["kind"]
    driver = importlib.import_module(f"perfbench.{kind}")
    device = common.device_info()
    out = driver.run(cell, args, t0)
    context = out["context"]
    context["peaks"] = peaks.for_device(peaks_for or device["kind"])
    device["memory_peak_bytes"] = out["memory_peak_bytes"]
    metrics: Dict[str, Dict[str, Any]] = {}
    result: Dict[str, Any] = {
        "correct": bool(out["checked"]["correct"]), "attempted": int(out["attempted"]),
        "failed": int(out["failed"]), "metrics": metrics, "device": device,
    }
    if args.trace:
        summary = context["trace"]
        device["busy_s"] = summary.busy_s()
        device["window_s"] = summary.window_s
        for metric in cell.per_layer():
            value = cell.reader(metric["name"])(context)
            if value is not None:
                metrics[metric["name"]] = {"value": float(value), "unit": metric["unit"]}
        result["breakdown"] = {"device_ops": summary.top_ops(10), "idle_gaps": summary.idle_gaps(10)}
    else:
        for metric in cell.end_to_end():
            if metric["name"] in out["e2e"]:
                metrics[metric["name"]] = {
                    "value": float(out["e2e"][metric["name"]]), "unit": metric["unit"]
                }
    result["workload"] = cell.name
    result["seed"] = args.seed
    # for the reader of a run by hand; the driver ignores these keys
    result["also"] = {k: v for k, v in out["e2e"].items() if isinstance(v, (int, float))}
    result["phases"] = context.get("phases", [])
    result["compared"] = {k: {"value": v[0], "limit": v[1]} for k, v in out["checked"]["numbers"].items()}
    return result


def main(argv: Optional[List[str]] = None) -> int:
    t_import = time.perf_counter()
    args = parse(argv)
    # the compile cache lives at a fixed path inside the checkout and evicts
    # nothing, whatever the machine's environment says: the program takes the
    # variable's directory, and a cell's programs outgrow a small cap
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    from perfbench import common, manifest

    t0 = min(common.process_start(), t_import)
    cell = manifest.Cell(manifest.load(), args.workload)
    require_tpu(cell.chips)
    result = execute(cell, args, t0)
    for name, pair in result["compared"].items():
        print(f"compared {name}: {pair['value']} (limit {pair['limit']})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result))
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())

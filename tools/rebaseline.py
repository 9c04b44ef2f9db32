"""Re-baseline bench.py from a confirmed on-TPU bench result.

``bench.py::BASELINE_EXAMPLES_PER_S`` carries a provisional B=32 number (770.0),
so a run with the now-default XLA attention dispatch would print a flattering
``vs_baseline``. Run this on the output of a successful ``bench.py`` run: if it
was a real accelerator measurement, the constant is rewritten to the measured
value, so every SUBSEQUENT run reports its ratio against the framework's own
best confirmed number rather than a stale one.

Guardrails: only TPU-backed results (the JSON line carries ``mfu``, which bench.py
emits only on accelerators), only values in a sane band for this benchmark, and
only upward moves beyond a 2% band (a re-baseline is a ratchet recording the best
confirmed state of the build, not a noisy tracker that would hide regressions —
a slower round SHOULD print vs_baseline < 1 against the best prior round).
"""

import json
import re
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
BENCH = REPO / "bench.py"
SANE_MIN, SANE_MAX = 300.0, 20000.0  # examples/s band for BERT-base seq-128 on one chip


def main() -> int:
    out_path = Path(sys.argv[1]) if len(sys.argv) > 1 else Path("/tmp/tpu_bench.out")
    try:
        line = out_path.read_text().strip().splitlines()[-1]
        result = json.loads(line)
    except (OSError, IndexError, ValueError) as exc:
        print(f"[rebaseline] no usable bench output at {out_path}: {exc}", file=sys.stderr)
        return 1
    if not isinstance(result, dict):
        print(f"[rebaseline] last output line is not a JSON object: {line!r}", file=sys.stderr)
        return 1
    try:
        value = float(result.get("value", 0.0))
    except (TypeError, ValueError):
        print(f"[rebaseline] non-numeric value field: {result.get('value')!r}", file=sys.stderr)
        return 1
    if result.get("metric") != "bert_base_finetune_throughput" or "mfu" not in result:
        print(f"[rebaseline] not an accelerator headline result: {line}", file=sys.stderr)
        return 1
    if not SANE_MIN <= value <= SANE_MAX:
        print(f"[rebaseline] value {value} outside sane band; refusing", file=sys.stderr)
        return 1

    src = BENCH.read_text()
    match = re.search(r"^BASELINE_EXAMPLES_PER_S = ([0-9.]+)$", src, re.M)
    if not match:
        print("[rebaseline] BASELINE_EXAMPLES_PER_S not found in bench.py", file=sys.stderr)
        return 1
    current = float(match.group(1))
    if value <= current * 1.02:
        print(
            f"[rebaseline] measured {value:.1f} within 2% of / below baseline {current:.1f}; keeping",
            file=sys.stderr,
        )
        return 0
    stamp = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
    src = src[: match.start()] + f"BASELINE_EXAMPLES_PER_S = {value:.1f}" + src[match.end():]
    # atomic swap: the driver's own bench.py run must never import a half-written
    # file (truncate-then-write would race it into a SyntaxError 0.0 headline)
    import os
    import tempfile

    fd, tmp = tempfile.mkstemp(dir=str(BENCH.parent), prefix=".bench.py.")
    try:
        with os.fdopen(fd, "w") as fh:
            fh.write(src)
        # mkstemp creates 0600; the driver's own `python bench.py` may run as a
        # different uid — preserve the original mode or it reads PermissionError
        os.chmod(tmp, os.stat(BENCH).st_mode & 0o7777)
        os.replace(tmp, BENCH)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    note = f"{stamp} rebaseline: BASELINE_EXAMPLES_PER_S {current:.1f} -> {value:.1f} (confirmed on-TPU bench.py run)"
    print(f"[rebaseline] {note}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())

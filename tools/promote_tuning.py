"""Distill kernel-sweep artifacts into the TUNING_MEASURED.json dispatch overlay.

Run after the kernel sweeps so a hardware run promotes its winners into the
auto-dispatch tables
(:mod:`unionml_tpu.ops.tuning` loads the overlay at import). Only
``timing_valid: true`` artifacts contribute — a CPU correctness sweep must
never overwrite on-device verdicts.

Artifact semantics: per shape, ``verdict`` says whether the pallas kernel beat
XLA's fused attention end to end (fwd+bwd), and ``best`` carries the winning
(block_q, block_k). Numerical-safety gate: a winner whose ``max_err_vs_xla``
exceeds bf16-rounding scale is never promoted.
"""

import json
import pathlib
import sys

REPO = pathlib.Path(__file__).resolve().parents[1]
MAX_PROMOTABLE_ERR = 0.25  # bf16 attention outputs: observed rounding is ~0.06
#: pallas must beat XLA by >2% to displace the default: single-window timings
#: carry noise at that scale, and a tie must break toward the
#: path the end-to-end arbiter validated
TIE_MARGIN = 0.98


def _shape_key(name: str):
    # sweep keys look like "b8_h12_s128_d64" (seq_q == seq_k in the sweeps)
    parts = {p[0]: p[1:] for p in name.split("_") if p}
    try:
        seq, dim = int(parts["s"]), int(parts["d"])
    except (KeyError, ValueError):
        return None
    return f"{seq},{seq},{dim}"


def _load(path: pathlib.Path):
    try:
        with open(path) as fh:
            payload = json.load(fh)
    except (OSError, ValueError):
        return None
    if not payload.get("timing_valid"):
        return None
    return payload.get("results", {})


_TABLES = (
    "measured_impl",
    "measured_packed_impl",
    "tuned_blocks",
    "packed_tuned_blocks",
    "measured_paged_impl",
)


def _paged_shape_key(name: str):
    # paged sweep keys look like "w16_bs16_h12_d64_int8"; the dtype suffix is
    # not part of the dispatch key (one traced program serves both pools)
    parts = {}
    for p in name.split("_"):
        if p.startswith("bs"):
            parts["bs"] = p[2:]
        elif p and p[0] in "whd" and p[1:].isdigit():
            parts[p[0]] = p[1:]
    try:
        return "{w},{bs},{h},{d}".format(**{k: int(v) for k, v in parts.items()})
    except (KeyError, ValueError):
        return None


def distill_paged(repo: pathlib.Path = REPO) -> dict:
    """PAGED_KERNEL_BENCH.json → measured_paged_impl.

    The paged default is PALLAS (the byte model carries the burden of proof the
    other way — see ``tuning.DEFAULT_PAGED_IMPL``), so the tie margin demotes
    toward pallas here: XLA must beat the kernel by >2% to claim the shape.
    Both pool dtypes share one dispatch key; the int8 verdict wins conflicts
    (it is the serving configuration the pool exists for)."""
    overlay = {"measured_paged_impl": {}}
    results = _load(repo / "PAGED_KERNEL_BENCH.json")
    if results is None:
        return overlay
    # int8 entries last so they overwrite the dense verdict on key conflicts
    for name in sorted(results, key=lambda n: n.endswith("int8")):
        entry = results[name]
        key = _paged_shape_key(name)
        verdict = entry.get("verdict")
        if key is None or verdict not in ("use_pallas", "use_xla", "pallas_failed_use_xla"):
            continue
        best = entry.get("best") or {}
        xla_ms = entry.get("xla_fwd_ms")
        if (
            verdict == "use_xla"
            and best
            and xla_ms
            and xla_ms > TIE_MARGIN * best.get("fwd_ms", float("inf"))
        ):
            print(f"[promote] paged {name}: xla within the tie margin "
                  f"({xla_ms} vs {best.get('fwd_ms')}ms); keeping pallas",
                  file=sys.stderr)
            verdict = "use_pallas"
        overlay["measured_paged_impl"][key] = (
            "pallas" if verdict == "use_pallas" else "xla"
        )
    return overlay


def distill(repo: pathlib.Path = REPO) -> dict:
    overlay = {name: {} for name in _TABLES}
    for artifact, impl_table, blocks_table in (
        ("KERNEL_BENCH.json", "measured_impl", "tuned_blocks"),
        ("PACKED_KERNEL_BENCH.json", "measured_packed_impl", "packed_tuned_blocks"),
    ):
        results = _load(repo / artifact)
        if results is None:
            continue
        for name, entry in results.items():
            key = _shape_key(name)
            verdict = entry.get("verdict")
            if key is None or verdict not in ("use_pallas", "use_xla", "pallas_failed_use_xla"):
                continue
            best = entry.get("best") or {}
            err = best.get("max_err_vs_xla", 0.0)
            if verdict == "use_pallas" and err > MAX_PROMOTABLE_ERR:
                print(f"[promote] {artifact} {name}: pallas won but err={err}; keeping xla",
                      file=sys.stderr)
                verdict = "use_xla"
            xla_ms = entry.get("xla_fwdbwd_ms")
            if (
                verdict == "use_pallas"
                and xla_ms
                and best.get("fwdbwd_ms", 0.0) > TIE_MARGIN * xla_ms
            ):
                print(f"[promote] {artifact} {name}: pallas within the tie margin "
                      f"({best.get('fwdbwd_ms')} vs {xla_ms}ms); keeping xla",
                      file=sys.stderr)
                verdict = "use_xla"
            overlay[impl_table][key] = "pallas" if verdict == "use_pallas" else "xla"
            # measured best blocks serve impl="pallas" even where xla won the
            # verdict (the documented escape hatch) — promote whenever the
            # winner is numerically safe
            if "block_q" in best and err <= MAX_PROMOTABLE_ERR:
                overlay[blocks_table][key] = [best["block_q"], best["block_k"]]
    return overlay


def main():
    overlay = distill(REPO)
    overlay.update(distill_paged(REPO))
    if not any(overlay.values()):
        print("[promote] no timing-valid sweep artifacts; overlay unchanged", file=sys.stderr)
        return
    out = REPO / "TUNING_MEASURED.json"
    # MERGE over the existing overlay: a window whose packed sweep failed (or ran
    # CPU-only) must not erase on-device packed verdicts a previous window earned
    merged = {name: {} for name in _TABLES}
    try:
        with open(out) as fh:
            existing = json.load(fh)
        for name in _TABLES:
            merged[name].update(existing.get(name) or {})
    except (OSError, ValueError):
        pass
    for name in _TABLES:
        merged[name].update(overlay[name])
    with open(out, "w") as fh:
        json.dump(merged, fh, indent=2, sort_keys=True)
    print(f"[promote] wrote {out}: "
          f"{len(merged['measured_impl'])} dense, "
          f"{len(merged['measured_packed_impl'])} packed, "
          f"{len(merged['measured_paged_impl'])} paged verdicts", file=sys.stderr)


if __name__ == "__main__":
    main()

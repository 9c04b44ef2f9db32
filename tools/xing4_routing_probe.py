"""Where a program in bfloat16 and the float32 reference part ways on Xing4.0,
and what a planted routing fault looks like beside that, at any size.

The benchmark's comparison (``perfbench/serve.py::check``) reads one number, the
widest gap by which a served token's float32 reference logit lies below the
reference's best. With experts that differ, one flipped top-k choice moves a
position's logits by a whole expert, so that number turns on how often a
near-tie of the router falls the other way, in any precision. This probe reads
what the comparison cannot print, on teacher-forced random sequences through
the program's full forward (the family's model and weights, as the cell makes
them) and through ``perfbench/reference/xing4.py``:

- per expert layer, the share of positions whose expert set differs from the
  float32 reference's: the program's own (its ``"routing"`` collection), the
  reference's in each lower precision, and each planted fault's;
- the largest float32 margin (last expert chosen over first left out) at which
  each of them first differs at a position (a later layer then sees another
  input), and how many positions differ among those kept: what
  ``reference_options.tie_margin`` has to exceed for the program and must not
  exceed for a control;
- the comparison's number for each, over all positions and over those the
  reference keeps at each ``--tie-margins`` value;
- routing load as a decode step of ``--step-rows`` rows sees it: experts hit a
  layer, the busiest expert's rows over the mean.

Faults, planted in the program's dispatch (``latent_moe.moe_apply_grouped``)
and never in the reference: ``next_expert`` (every pair goes to the expert
after the one chosen), ``sparse_next_expert`` (every 64th position only),
``weights_rolled`` (a position's weights paired with the wrong experts of its
own set: a wrong unsort).

On the chip, at the cell's size::

    python3 tools/xing4_routing_probe.py --seeds 11,12 --out chiprun_out/xing4_probe.json

On the CPU, at a size that runs in minutes::

    JAX_PLATFORMS=cpu python3 tools/xing4_routing_probe.py --tokens 512 --set hidden_size=256 \\
        --set vocab_size=4096 --set intermediate_size=512 --set moe_intermediate_size=128 \\
        --set q_lora_rank=96 --set kv_lora_rank=64 --set num_attention_heads=4
"""

from __future__ import annotations

import argparse
import contextlib
import json
import pathlib
import sys
from typing import Any, Dict, Iterator, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from perfbench.families import xing4 as family  # noqa: E402
from perfbench.reference import xing4 as reference  # noqa: E402
from unionml_tpu.models import latent_moe  # noqa: E402

FAULTS = ("next_expert", "sparse_next_expert", "weights_rolled")


@contextlib.contextmanager
def planted(fault: str, experts: int) -> Iterator[None]:
    """The program's grouped dispatch with ``fault`` in it, while tracing."""
    sound = latent_moe.moe_apply_grouped

    def faulty(fn, params, rows, chosen, weights):
        if fault == "next_expert":
            chosen = (chosen + 1) % experts
        elif fault == "sparse_next_expert":
            hit = (jnp.arange(chosen.shape[0]) % 64 == 0)[:, None]
            chosen = jnp.where(hit, (chosen + 1) % experts, chosen)
        elif fault == "weights_rolled":
            weights = jnp.roll(weights, 1, axis=-1)
        else:
            raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")
        return sound(fn, params, rows, chosen, weights)

    latent_moe.moe_apply_grouped = faulty
    try:
        yield
    finally:
        latent_moe.moe_apply_grouped = sound


_PROGRAMS: Dict[str, Any] = {}


def program(config: Dict[str, Any], fault: str = ""):
    """``ids (1, T) -> (argmax tokens (T,), [chosen (T, k) per expert layer])``,
    one compiled program a fault for all seeds."""
    if fault not in _PROGRAMS:
        _PROGRAMS[fault] = _program(config, fault)
    return _PROGRAMS[fault]


def _program(config: Dict[str, Any], fault: str):
    model = family.model(config)
    expert_layers = range(config["first_k_dense_replace"], config["layers"])

    def run(params, ids):
        logits, sown = model.apply({"params": params}, ids, mutable=["routing"])
        chosen = [sown["routing"][f"layer_{i}"]["moe"]["chosen"][0] for i in expert_layers]
        return jnp.argmax(logits[0], axis=-1), chosen

    jitted = jax.jit(run)
    if not fault:
        return jitted

    def traced_with_fault(params, ids):
        with planted(fault, config["n_routed_experts"]):
            return jitted(params, ids)

    return traced_with_fault


def differs(chosen: np.ndarray, want: np.ndarray) -> np.ndarray:
    """(T,) bool: the expert set of a position is not the reference's."""
    return (np.sort(chosen, axis=-1) != np.sort(want, axis=-1)).any(axis=-1)


def step_load(chosen: np.ndarray, experts: int, step_rows: int) -> Tuple[float, float]:
    """Mean experts hit, and mean busiest-over-mean, over groups of ``step_rows`` positions."""
    groups = chosen[: len(chosen) // step_rows * step_rows].reshape(-1, step_rows * chosen.shape[-1])
    counts = np.stack([np.bincount(group, minlength=experts) for group in groups])
    return float((counts > 0).sum(axis=1).mean()), float((counts.max(axis=1) * experts / groups.shape[1]).mean())


def probe(config: Dict[str, Any], seed: int, args) -> Dict[str, Any]:
    deployment = config["perfbench"]
    params = family.make_params(config, seed, deployment["weights_dtype"])
    kw = {k: v for k, v in family.reference_kwargs(config).items() if k != "tie_margin"}
    kw["query_block"] = min(kw["query_block"], args.tokens)
    ids = jax.random.randint(jax.random.PRNGKey(seed), (1, args.tokens), 0, config["vocab_size"], jnp.int32)
    rows = jnp.arange(args.tokens // 2, args.tokens)  # positions with a context behind them
    first = int(rows[0])
    experts = config["n_routed_experts"]

    want_logits, want_chosen, margins = reference.forward(params, ids, rows, **kw)
    want_chosen = [np.asarray(c) for c in want_chosen]
    margins = np.stack([np.asarray(m) for m in margins])  # (layers, rows)
    narrowest = margins.min(axis=0)
    best = jnp.max(want_logits, axis=-1)
    picked = jax.jit(lambda logits, tokens: jnp.take_along_axis(logits, tokens[:, None], axis=-1)[:, 0])

    def gap_of(tokens):
        return best - picked(want_logits, tokens)

    hit, skew = zip(*(step_load(c, experts, args.step_rows) for c in want_chosen))
    out: Dict[str, Any] = {
        "seed": seed, "rows": int(rows.size), "experts_hit_a_layer": hit, "load_max_over_mean": skew,
        "margin_quantiles": {q: float(np.quantile(margins, q)) for q in (0.01, 0.05, 0.1, 0.25, 0.5)},
        "rows_kept": {str(t): int((narrowest >= t).sum()) for t in args.tie_margins},
        "variants": {},
    }

    def record(name: str, tokens: jax.Array, chosen: List[np.ndarray]) -> None:
        gaps = np.asarray(gap_of(tokens))
        wrong = np.stack([differs(c, w) for c, w in zip(chosen, want_chosen)])  # (layers, rows)
        out["variants"][name] = {
            "sets_differ_share_by_layer": [float(w.mean()) for w in wrong],
            # a position's first layer that differs: later ones see another input
            "widest_margin_of_a_first_difference": float(
                np.where(wrong & (np.cumsum(wrong, axis=0) == 1), margins, 0.0).max()
            ),
            "kept_positions_with_a_differing_set": {
                str(t): int(((narrowest >= t) & wrong.any(axis=0)).sum()) for t in args.tie_margins
            },
            "logit_gap": {
                str(t): float(np.where(narrowest >= t, gaps, 0.0).max()) for t in args.tie_margins
            },
            "positions_over": {
                str(t): int(((narrowest >= t) & (gaps > args.limit)).sum()) for t in args.tie_margins
            },
        }
        print(json.dumps({"seed": seed, "variant": name, **out["variants"][name]}), flush=True)

    print(json.dumps({k: v for k, v in out.items() if k != "variants"}), flush=True)
    for name in args.variants:
        if name in ("program",) + FAULTS:
            tokens, chosen = program(config, "" if name == "program" else name)(params, ids)
            record(name, tokens[first:], [np.asarray(c)[first:] for c in chosen])
        else:
            logits, chosen, _ = reference.forward(params, ids, rows, lowp=name, **kw)
            record(name, jnp.argmax(logits, axis=-1), [np.asarray(c) for c in chosen])
            del logits
    return out


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--config", default=str(ROOT / "perfbench/configs/xing4-29b-a4b-serve.json"))
    parser.add_argument("--seeds", default="1", help="comma-separated; weights and token ids come from each")
    parser.add_argument("--tokens", type=int, default=2048, help="sequence length; its second half is compared")
    parser.add_argument("--step-rows", type=int, default=32, help="rows of a decode step, for the load numbers")
    parser.add_argument("--variants", default="program,bf16,fp8,int8," + ",".join(FAULTS))
    parser.add_argument("--tie-margins", default="0,0.0005,0.001,0.002,0.004,0.008")
    parser.add_argument("--limit", type=float, default=None, help="the cell's logit_gap limit (default: its limits file)")
    parser.add_argument("--set", action="append", default=[], metavar="KEY=VALUE", help="a published size, overridden")
    parser.add_argument("--init", action="append", default=[], metavar="KEY=VALUE", help="a key of perfbench.init")
    parser.add_argument("--out", default="")
    args = parser.parse_args()

    config = json.loads(pathlib.Path(args.config).read_text())
    config.update({k: json.loads(v) for k, v in (item.split("=", 1) for item in args.set)})
    config["perfbench"].setdefault("init", {}).update(
        {k: json.loads(v) for k, v in (item.split("=", 1) for item in args.init)}
    )
    args.variants = [name for name in args.variants.split(",") if name]
    args.tie_margins = [float(t) for t in args.tie_margins.split(",")]
    if args.limit is None:
        limits = ROOT / "perfbench/limits/xing4-29b-a4b.fewshot-closed.json"
        args.limit = float(json.loads(limits.read_text())["logit_gap"])
    print(json.dumps({"backend": jax.default_backend(), "devices": [d.device_kind for d in jax.devices()]}), flush=True)
    results = [probe(config, int(seed), args) for seed in args.seeds.split(",")]
    if args.out:
        path = pathlib.Path(args.out)
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps({"init": config["perfbench"]["init"], "results": results}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())

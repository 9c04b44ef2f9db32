"""MFU experiment sweep: measure throughput variants of the headline BERT-base step.

Run on a real TPU. Each variant times the same fine-tune step with one knob
changed; MFU_SWEEP.json records the whole sweep (every variant's result or error,
with a timestamp) so winners can be promoted into bench.py / model defaults with
measured justification.

Variants:
- batch ladder: B=64 (headline), 128, 256 — MXU tiles grow with batch
- gelu tanh-approximate vs exact erf (VPU-bound candidate)
- no attention mask (quantifies the all-ones-mask overhead the headline pays)
- metrics-light (no grad_norm metric — tests the XLA-CSE-merges-the-norms assumption)
- S=512 at B=16 (same token count as B=64/S=128; long-seq regime)

CPU smoke: runs the tiny config so the harness itself stays testable.
"""

import json
import os
import sys
import time

#: whole-sweep wall-clock budget; variants still pending when it expires are skipped
TOTAL_BUDGET_S = float(os.getenv("UNIONML_MFU_BUDGET", "600"))


def _measure(step, state, batch, batch_size, warmup=3, steps=15):
    import jax

    for _ in range(warmup):
        state, metrics = step(state, batch)
    jax.block_until_ready(metrics)
    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = step(state, batch)
    jax.block_until_ready(metrics)
    elapsed = time.perf_counter() - t0
    return steps * batch_size / elapsed


def run_sweep():
    import jax

    from unionml_tpu.utils import configure_compile_cache

    configure_compile_cache()

    import jax.numpy as jnp
    import numpy as np

    from bench import _chip_peak_flops
    from unionml_tpu.models import (
        BertConfig,
        BertForSequenceClassification,
        create_train_state,
        init_params,
    )
    from unionml_tpu.models.training import bert_flops_per_token, make_classifier_train_step

    on_accel = jax.default_backend() not in ("cpu",)
    peak = _chip_peak_flops() if on_accel else None
    deadline = time.monotonic() + TOTAL_BUDGET_S

    if on_accel:
        base = dict(dtype=jnp.bfloat16)
        variants = [
            ("b64_headline", dict(batch=64, seq=128)),
            ("b128", dict(batch=128, seq=128)),
            ("b256", dict(batch=256, seq=128)),
            ("b64_gelu_tanh", dict(batch=64, seq=128, config=dict(gelu_approximate=True))),
            ("b64_nomask", dict(batch=64, seq=128, mask=False)),
            ("b64_no_gradnorm_metric", dict(batch=64, seq=128, light_metrics=True)),
            ("s512_b16", dict(batch=16, seq=512)),
            # remat trades recompute FLOPs for HBM: the batch sizes the plain
            # ladder OOMs at become reachable, where MXU tiles are largest
            ("b256_remat", dict(batch=256, seq=128, config=dict(remat=True))),
            ("b512_remat", dict(batch=512, seq=128, config=dict(remat=True))),
            # accumulation: biggest logical batch at one-quarter the activation
            # memory — the fallback if plain b512_remat OOMs
            ("b512_remat_accum4", dict(batch=512, seq=128, config=dict(remat=True), grad_accum=4)),
            # bf16 adam first moment: halves mu HBM traffic in the optimizer step
            ("b256_remat_bf16mu", dict(batch=256, seq=128, config=dict(remat=True), bf16_mu=True)),
            # long-seq large-batch: biggest fused attention windows the chip holds
            ("s512_b64_remat", dict(batch=64, seq=512, config=dict(remat=True))),
        ]
        config_cls = BertConfig.base
    else:  # CPU smoke of the harness itself
        base = dict(dtype=jnp.float32, attention_impl="xla")
        variants = [
            ("b8_smoke", dict(batch=8, seq=128)),
            ("b8_gelu_tanh", dict(batch=8, seq=128, config=dict(gelu_approximate=True))),
            ("b8_bf16mu", dict(batch=8, seq=128, bf16_mu=True)),
        ]
        config_cls = BertConfig.tiny

    rng = np.random.default_rng(0)
    results = []
    for name, spec in variants:
        if time.monotonic() > deadline:
            print(f"[mfu] budget exhausted; skipping {name} onward", file=sys.stderr)
            break
        try:
            cfg_overrides = dict(base)
            cfg_overrides.update(spec.get("config", {}))
            config = config_cls(**cfg_overrides)
            batch_size, seq_len = spec["batch"], spec["seq"]
            model = BertForSequenceClassification(config)
            variables = init_params(config, seq_len=seq_len)
            state = create_train_state(
                model, variables, learning_rate=2e-5, warmup_steps=10, total_steps=1000,
                mu_dtype=jnp.bfloat16 if spec.get("bf16_mu") else None,
            )
            step = make_classifier_train_step(
                input_signature=("input_ids", "attention_mask") if spec.get("mask", True) else ("input_ids",),
                light_metrics=spec.get("light_metrics", False),
                grad_accum=spec.get("grad_accum", 1),
            )
            batch = {
                "input_ids": jnp.asarray(
                    rng.integers(0, config.vocab_size, size=(batch_size, seq_len)), dtype=jnp.int32
                ),
                "labels": jnp.asarray(
                    rng.integers(0, config.num_labels, size=(batch_size,)), dtype=jnp.int32
                ),
            }
            if spec.get("mask", True):
                batch["attention_mask"] = jnp.ones((batch_size, seq_len), dtype=jnp.int32)
            t_compile = time.monotonic()
            examples_per_s = _measure(step, state, batch, batch_size)
            tokens_per_s = examples_per_s * seq_len
            mfu = (
                tokens_per_s * bert_flops_per_token(config) / peak if peak else None
            )
            entry = {
                "variant": name,
                "examples_per_s": round(examples_per_s, 1),
                "tokens_per_s": round(tokens_per_s),
                "batch": batch_size,
                "seq": seq_len,
                "wall_s": round(time.monotonic() - t_compile, 1),
            }
            if mfu is not None:
                entry["mfu"] = round(mfu, 4)
            results.append(entry)
            print(f"[mfu] {json.dumps(entry)}", file=sys.stderr)
        except Exception as exc:
            print(f"[mfu] {name} failed: {type(exc).__name__}: {exc}", file=sys.stderr)
            results.append({"variant": name, "error": f"{type(exc).__name__}: {exc}"})
    return results


def main():
    import jax

    results = run_sweep()
    payload = {
        "sweep": "bert_base_train_step_variants",
        "stamp": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "backend": jax.default_backend(),
        "results": results,
    }
    # accelerator runs own MFU_SWEEP.json — including all-errors sweeps, whose
    # error entries + stamp must replace stale numbers rather than impersonate
    # them; CPU smoke runs divert to the _cpu sibling (shared bench policy)
    from bench_util import resolve_artifact_path

    out_path = resolve_artifact_path(
        os.path.join(os.path.dirname(os.path.abspath(__file__)), "MFU_SWEEP.json"),
        payload["backend"],
    )
    # accelerator artifact only when the sweep produced numbers or errors (an
    # entirely-empty sweep must not blank a prior real one); _cpu always writes
    if payload["backend"] == "cpu" or any("mfu" in r or "error" in r for r in results):
        with open(out_path, "w") as fh:
            json.dump(payload, fh, indent=2)
    print(json.dumps(payload))


if __name__ == "__main__":
    main()

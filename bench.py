"""Benchmark: BERT-base fine-tune step throughput.

Prints exactly ONE JSON line to stdout:
    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

The reference (unionai-oss/unionml) publishes no performance numbers anywhere,
so the baseline is this framework's own earlier measurement on a v5e chip;
``vs_baseline`` is the ratio current/baseline.

Method: synthetic tokenized batches (seq 128), jit-compiled train step with donated
state, bfloat16 compute; warmup steps excluded, steady-state examples/s reported.
All logging goes to stderr; stdout carries only the JSON line.
"""

import json
import logging
import os
import sys
import time

logging.basicConfig(stream=sys.stderr)
for noisy in ("jax", "unionml_tpu"):
    logging.getLogger(noisy).setLevel(logging.WARNING)

#: the framework's best CONFIRMED on-TPU measurement of this benchmark
#: (examples/s), from a v5e-1 run of an earlier round (BERT-base bf16, B=32,
#: seq 128); ratcheted by tools/rebaseline.py after a successful on-TPU run.
#: vs_baseline is current / best-confirmed-prior; the emitted
#: ``baseline_examples_per_s`` field keeps the ratio self-describing.
BASELINE_EXAMPLES_PER_S = 770.0


#: peak dense bf16 TFLOP/s per chip for MFU accounting (public spec sheets).
#: Keys match jax device_kind with spaces stripped — real strings look like
#: "TPU v5 lite" / "TPU v5p" / "TPU v4"; order matters (most specific first).
_CHIP_PEAK_TFLOPS = (
    ("v5lite", 197.0),  # v5e reports device_kind "TPU v5 lite"
    ("v5e", 197.0),
    ("v5p", 459.0),
    ("v6lite", 918.0),  # v6e / Trillium
    ("v6e", 918.0),
    ("v4", 275.0),
)


def _chip_peak_flops():
    """Peak FLOP/s of the local chip, or None when unknown (logged)."""
    try:
        import jax

        kind = jax.devices()[0].device_kind.lower().replace(" ", "")
    except Exception:  # graftlint: disable=swallowed-exception -- unknown backend/device_kind simply means "no peak-FLOPs denominator": MFU is omitted, not wrong
        return None
    for name, tflops in _CHIP_PEAK_TFLOPS:
        if name in kind:
            return tflops * 1e12
    print(f"[bench] unrecognized device_kind {kind!r}: MFU omitted.", file=sys.stderr)
    return None


def _emit_zero_and_exit(reason: str):
    print(f"[bench] {reason}; emitting a zero result.", file=sys.stderr)
    print(
        json.dumps(
            {
                "metric": "bert_base_finetune_throughput",
                "value": 0.0,
                "unit": "examples/s",
                "vs_baseline": 0.0,
            }
        ),
        flush=True,
    )
    os._exit(1)


def run_bench():
    import jax

    from unionml_tpu.utils import configure_compile_cache

    configure_compile_cache()

    import jax.numpy as jnp
    import numpy as np

    from unionml_tpu.models import (
        BertConfig,
        BertForSequenceClassification,
        create_train_state,
        init_params,
    )
    from unionml_tpu.models.training import bert_flops_per_token, make_classifier_train_step

    backend = jax.default_backend()
    on_accelerator = backend not in ("cpu",)
    if on_accelerator:
        config = BertConfig.base(dtype=jnp.bfloat16)
        # B=64 first; the ladder falls back on OOM
        batch_sizes = (64, 32, 16, 8)
        measure_steps, warmup_steps = 20, 3
    else:  # keep the CPU path runnable for smoke testing
        config = BertConfig.tiny(dtype=jnp.float32, attention_impl="xla")
        batch_sizes = (8,)
        measure_steps, warmup_steps = 5, 1

    seq_len = 128
    model = BertForSequenceClassification(config)
    rng = np.random.default_rng(0)

    last_error = None
    for batch_size in batch_sizes:
        try:
            variables = init_params(config, seq_len=seq_len)
            state = create_train_state(
                model, variables, learning_rate=2e-5, warmup_steps=10, total_steps=1000
            )
            step = make_classifier_train_step(input_signature=("input_ids", "attention_mask"))
            batch = {
                "input_ids": jnp.asarray(
                    rng.integers(0, config.vocab_size, size=(batch_size, seq_len)), dtype=jnp.int32
                ),
                "attention_mask": jnp.ones((batch_size, seq_len), dtype=jnp.int32),
                "labels": jnp.asarray(rng.integers(0, config.num_labels, size=(batch_size,)), dtype=jnp.int32),
            }
            for _ in range(warmup_steps):
                state, metrics = step(state, batch)
            jax.block_until_ready(metrics)

            t0 = time.perf_counter()
            for _ in range(measure_steps):
                state, metrics = step(state, batch)
            jax.block_until_ready(metrics)
            elapsed = time.perf_counter() - t0

            examples_per_s = measure_steps * batch_size / elapsed
            tokens_per_s = examples_per_s * seq_len
            flops_per_token = bert_flops_per_token(config)
            achieved_flops = tokens_per_s * flops_per_token
            mfu = None
            peak = _chip_peak_flops()
            if on_accelerator and peak:
                mfu = achieved_flops / peak
            print(
                f"[bench] backend={backend} batch={batch_size} steps={measure_steps} "
                f"elapsed={elapsed:.2f}s examples/s={examples_per_s:.1f} "
                f"tokens/s={tokens_per_s:.0f} ~TFLOP/s={achieved_flops/1e12:.2f}"
                + (f" MFU={mfu:.1%}" if mfu is not None else ""),
                file=sys.stderr,
            )
            return examples_per_s, mfu
        except Exception as exc:  # OOM etc: try a smaller batch
            last_error = exc
            print(f"[bench] batch={batch_size} failed: {exc}", file=sys.stderr)
    raise RuntimeError(f"benchmark failed at all batch sizes: {last_error}")


def main():
    try:
        value, mfu = run_bench()
    except BaseException as exc:  # noqa: BLE001 — the JSON-line contract beats a traceback
        _emit_zero_and_exit(f"benchmark raised {type(exc).__name__}: {exc}")
    vs_baseline = value / BASELINE_EXAMPLES_PER_S if BASELINE_EXAMPLES_PER_S else 1.0
    payload = {
        "metric": "bert_base_finetune_throughput",
        "value": round(value, 2),
        "unit": "examples/s",
        "vs_baseline": round(vs_baseline, 3),
        # the denominator, so the ratio is self-describing: the best confirmed
        # prior on-TPU measurement, ratcheted by tools/rebaseline.py after each
        # successful battery run — see BASELINE_EXAMPLES_PER_S
        "baseline_examples_per_s": BASELINE_EXAMPLES_PER_S,
    }
    if mfu is not None:
        payload["mfu"] = round(mfu, 4)
    print(json.dumps(payload))


if __name__ == "__main__":
    main()

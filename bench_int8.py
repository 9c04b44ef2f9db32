"""int8 decode at scale: does it pay at ~1B params?

An earlier lookahead probe found int8 neutral-to-slightly-slower at GPT-2
small (124M): dequant overhead ~= weight-traffic savings. The claim that it
PAYS where decode is
weight-bound — >=1B params — has never been measured. This harness builds a
~1.3B-param randomly-initialized GPT (weight TRAFFIC is what decode time
measures; weight values are irrelevant), runs the continuous engine's
single-stream decode with and without ``quantize="int8"``, plus the PR-14
combined arm (int8 weights over an int8 paged KV pool, ``kv_quantize``),
and records tokens/s and resident bytes for all three into
``INT8_BENCH.json``. Byte accounting reuses the ops.quant helpers
(``quantized_bytes``) and the engine's ``kv_pool_stats()`` — the same
numbers the serving telemetry gauges export.

CPU smoke uses the tiny config so the harness itself stays testable.
"""

import json
import os
import sys
import time

TOTAL_BUDGET_S = float(os.getenv("UNIONML_INT8_BUDGET", "540"))


def run():
    import jax

    from unionml_tpu.utils import configure_compile_cache

    configure_compile_cache()

    import jax.numpy as jnp
    import numpy as np

    from unionml_tpu.models.gpt import GPTConfig, GPTLMHeadModel, init_params
    from unionml_tpu.serving.continuous import DecodeEngine

    on_accel = jax.default_backend() not in ("cpu",)
    if on_accel:
        # ~1.3B params: 24 x 2048 with GPT-2 vocab (12*h^2*L + vocab*h)
        config = GPTConfig(
            vocab_size=50257, hidden_size=2048, num_layers=24, num_heads=16,
            max_position_embeddings=256, dropout=0.0, dtype=jnp.bfloat16,
        )
        max_new, lookahead = 64, 8
    else:
        config = GPTConfig.tiny(dropout=0.0, dtype=jnp.float32, attention_impl="xla")
        max_new, lookahead = 16, 4

    model = GPTLMHeadModel(config)
    t0 = time.monotonic()
    variables = init_params(config, seq_len=16)
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(variables))
    print(f"[int8] init {n_params/1e9:.2f}B params in {time.monotonic() - t0:.0f}s", file=sys.stderr)
    deadline = time.monotonic() + TOTAL_BUDGET_S

    from unionml_tpu.ops.quant import quantized_bytes

    prompt = [3, 1, 4, 1, 5]
    results = {"params_b": round(n_params / 1e9, 3), "max_new_tokens": max_new,
               "lookahead": lookahead}
    MAX_LEN, BS = 128, 4
    arms = (
        ("bf16", {}),
        ("int8", {"quantize": "int8"}),
        # the PR-14 serving config: int8 weights AND an int8 paged KV pool
        ("int8_kv8", {"quantize": "int8", "paged": True,
                      "pool_blocks": MAX_LEN // BS + 1, "prefix_block_size": BS,
                      "prefix_cache_blocks": 0, "kv_quantize": "int8"}),
    )
    for name, extra in arms:
        if time.monotonic() > deadline:
            results[name] = {"error": "budget exhausted"}
            continue
        try:
            engine = DecodeEngine(
                model, variables, num_slots=1, max_len=MAX_LEN, prefill_buckets=(8,),
                **extra,
            )
            # warm: one full completion compiles prefill + decode
            engine.generate(prompt, max_new, lookahead=lookahead)
            t1 = time.perf_counter()
            reps = 3
            for _ in range(reps):
                tokens = engine.generate(prompt, max_new, lookahead=lookahead)
            elapsed = time.perf_counter() - t1
            tok_s = reps * len(tokens) / elapsed
            results[name] = {"tokens_per_s": round(tok_s, 1), "reps": reps}
            if extra.get("quantize"):
                stored, full = quantized_bytes(engine._variables)
                results[name]["weight_bytes_stored"] = int(stored)
                results[name]["weight_bytes_dense_equiv"] = int(full)
            kv = engine.kv_pool_stats()
            if kv:
                results[name]["kv_dtype"] = kv["kv_dtype"]
                results[name]["kv_pool_bytes"] = kv["kv_pool_bytes"]
                results[name]["kv_pool_bytes_dense_equiv"] = kv["kv_pool_bytes_dense_equiv"]
            print(f"[int8] {name}: {tok_s:.1f} tok/s", file=sys.stderr)
        except Exception as exc:
            results[name] = {"error": f"{type(exc).__name__}: {exc}"}
            print(f"[int8] {name} failed: {exc}", file=sys.stderr)
    for name in ("int8", "int8_kv8"):
        if "tokens_per_s" in results.get("bf16", {}) and "tokens_per_s" in results.get(name, {}):
            results[f"{name}_speedup"] = round(
                results[name]["tokens_per_s"] / results["bf16"]["tokens_per_s"], 3
            )
    return results


def main():
    results = run()
    import jax

    payload = {
        "metric": "int8_decode_at_scale",
        "backend": jax.default_backend(),
        "recorded_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        **results,
    }
    from bench_util import resolve_artifact_path

    out_path = resolve_artifact_path(
        os.path.join(os.path.dirname(os.path.abspath(__file__)), "INT8_BENCH.json"),
        payload["backend"],
    )
    with open(out_path, "w") as fh:
        json.dump(payload, fh, indent=2)
    print(json.dumps(payload))


if __name__ == "__main__":
    main()

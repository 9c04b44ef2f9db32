"""Serving-latency microbench: resident-predictor p50/p99.

Three measurements, single-row requests each:

1. **digits-style MLP, in-process** — feature pipeline, pad-to-bucket, resident
   compiled executable, device->host (the reference quickstart shape,
   ``unionml/fastapi.py:50-64`` hot path);
2. **BERT classifier, in-process** — tokenized dict features exercising
   sequence-length bucketing (the multi-input warmup path VERDICT round-1 flagged);
3. **digits-style MLP over HTTP** — the same model behind the real aiohttp server,
   measuring the full served path end to end.

Cold-start (compilation) is excluded: each app takes one untimed warm request first.
Writes ``SERVING_BENCH.json`` (committed artifact) and prints one JSON line per model.
On CPU the BERT entry uses a scaled-down config; on real TPU pass ``--bert-base``.
Not driver-invoked (bench.py carries the headline metric).
"""

import argparse
import json
import sys
import time
from datetime import datetime, timezone

import numpy as np


def _measure(fn, iters=200):
    fn()  # warm request: compile + caches, excluded from stats
    latencies = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        latencies.append((time.perf_counter() - t0) * 1e3)
    latencies.sort()
    return {
        "p50_ms": round(latencies[len(latencies) // 2], 3),
        "p90_ms": round(latencies[int(len(latencies) * 0.90)], 3),
        "p99_ms": round(latencies[min(int(len(latencies) * 0.99), len(latencies) - 1)], 3),
        "iters": iters,
    }


class _RetraceCounter:
    """Counts jaxpr traces (jit cache misses) across a timed window.

    Hooks ``jax.monitoring``'s duration events: every compile records a
    ``/jax/core/compile/jaxpr_trace_duration`` event, so the count across a
    bench window is exactly the number of retraces the workload paid — the
    measured number graftlint's ``retrace`` rule findings correlate with
    (ISSUE 4 satellite). A steady-state window after warmup should report 0;
    admission windows report the (bounded) bucket-ladder compiles.
    """

    EVENT = "/jax/core/compile/jaxpr_trace_duration"

    def __init__(self) -> None:
        self.count = 0

    def _listener(self, name, *args, **kwargs):
        if name == self.EVENT:
            self.count += 1

    def __enter__(self) -> "_RetraceCounter":
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._listener)
        return self

    def __exit__(self, *exc) -> None:
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self._listener)


def _build_mlp_model(name: str):
    """The shared 64-feature MLP app both MLP benches measure (keep them comparable)."""
    import jax
    import jax.numpy as jnp
    import pandas as pd

    from unionml_tpu import Dataset, Model

    n_features = 64
    feature_names = [f"f{i}" for i in range(n_features)]
    dataset = Dataset(name=f"{name}_ds", features=feature_names, targets=["y"], device_format="jax")

    def init(scale: float = 1.0) -> dict:
        rng = np.random.default_rng(0)
        return {
            "w1": jnp.asarray(rng.normal(size=(n_features, 128)) * 0.1, dtype=jnp.float32),
            "w2": jnp.asarray(rng.normal(size=(128, 10)) * 0.1, dtype=jnp.float32),
        }

    model = Model(name=name, init=init, dataset=dataset)

    @dataset.reader
    def reader(n: int = 256) -> pd.DataFrame:
        rng = np.random.default_rng(0)
        frame = pd.DataFrame(rng.normal(size=(n, n_features)).astype(np.float32), columns=feature_names)
        frame["y"] = rng.integers(0, 10, size=n)
        return frame

    @model.trainer
    def trainer(params: dict, X: jax.Array, y: jax.Array) -> dict:
        return params

    @model.predictor
    def predictor(params: dict, X: jax.Array) -> jax.Array:
        return jnp.argmax(jax.nn.relu(X @ params["w1"]) @ params["w2"], axis=-1)

    @model.evaluator
    def evaluator(params: dict, X: jax.Array, y: jax.Array) -> float:
        return 0.0

    return model, feature_names


def bench_mlp():
    from unionml_tpu.serving import ResidentPredictor

    model, feature_names = _build_mlp_model("bench_model")
    model.train()
    resident = ResidentPredictor(model, warmup=True)
    resident.setup()

    request = [dict(zip(feature_names, np.random.default_rng(1).normal(size=64)))]
    stats = _measure(lambda: resident.predict(features=request))
    # device-vs-end-to-end split (VERDICT r3 #8): the resident predictor's own
    # timer covers dispatch + device->host fetch only (no feature pipeline);
    # 'count' is dropped like bench_http does (it differs from iters by the
    # warm request and would read as a conflicting iteration count)
    stats.update({k: v for k, v in resident.device_stats().items() if k != "count"})
    return stats


def bench_bert(base: bool = False, seq_bucket: int = 128):
    import jax
    import jax.numpy as jnp

    from unionml_tpu import Dataset, Model
    from unionml_tpu.models.bert import BertConfig, BertForSequenceClassification, init_params
    from unionml_tpu.serving import ResidentPredictor

    if base:
        config = BertConfig.base(dtype=jnp.bfloat16, hidden_dropout=0.0, attention_dropout=0.0)
    else:
        # CPU-scale stand-in: 4 layers x 256 hidden — big enough that compute, not
        # dispatch, dominates; the shape pipeline is identical to base
        config = BertConfig(
            vocab_size=8192,
            hidden_size=256,
            num_layers=4,
            num_heads=4,
            intermediate_size=1024,
            max_position_embeddings=seq_bucket,
            dtype=jnp.float32,
            attention_impl="xla",
            hidden_dropout=0.0,
            attention_dropout=0.0,
        )
    bert = BertForSequenceClassification(config)
    variables = init_params(config, seq_len=seq_bucket)

    dataset = Dataset(name="bert_bench_ds", targets=["y"], device_format="jax")

    import pandas as pd

    @dataset.reader
    def reader(n: int = 8) -> pd.DataFrame:
        return pd.DataFrame({"text": ["x"] * n, "y": [0] * n})

    from typing import Dict as _Dict

    @dataset.feature_loader
    def feature_loader(raw) -> _Dict[str, np.ndarray]:
        if isinstance(raw, dict):
            return raw
        # hash-"tokenize" client rows [{"text": ...}] to fixed-width id arrays
        texts = [r["text"] if isinstance(r, dict) else str(r) for r in raw]
        width = max(len(t.split()) for t in texts)
        ids = np.zeros((len(texts), width), dtype=np.int32)
        mask = np.zeros((len(texts), width), dtype=np.int32)
        for i, t in enumerate(texts):
            toks = [hash(w) % (config.vocab_size - 1) + 1 for w in t.split()]
            ids[i, : len(toks)] = toks
            mask[i, : len(toks)] = 1
        return {"input_ids": ids, "attention_mask": mask}

    model = Model(name="bert_bench", init=lambda: variables["params"], dataset=dataset)

    import jax as _jax

    @model.trainer
    def trainer(params: dict, X: _jax.Array, y: _jax.Array) -> dict:
        return params

    @model.predictor
    def predictor(params: dict, features: _Dict[str, np.ndarray]) -> _jax.Array:
        logits = bert.apply(
            {"params": params},
            features["input_ids"],
            features["attention_mask"],
            deterministic=True,
        )
        return jnp.argmax(logits, axis=-1)

    @model.evaluator
    def evaluator(params: dict, X: _jax.Array, y: _jax.Array) -> float:
        return 0.0

    from unionml_tpu.model import ModelArtifact

    model.artifact = ModelArtifact(variables["params"], None, None)

    words = " ".join(f"w{i}" for i in range(37))  # 37-token request, pads to seq_bucket
    example = [{"text": words}]
    resident = ResidentPredictor(
        model,
        buckets=(1, 2, 4, 8),
        seq_buckets=(seq_bucket,),
        example_features=example,
        warmup=True,
    )
    resident.setup()
    stats = _measure(lambda: resident.predict(features=example), iters=100)
    stats.update({k: v for k, v in resident.device_stats().items() if k != "count"})
    return stats


def _serve_app(app):
    """Boot an aiohttp app on a background thread; returns ``(port, stop)``.

    ``stop()`` tears the runner/loop/thread down. Bind/setup failures propagate
    to the caller. Shared by every HTTP bench phase."""
    import asyncio
    import threading

    from aiohttp import web

    from unionml_tpu.utils import pick_free_port

    port = pick_free_port()
    loop = asyncio.new_event_loop()
    started = threading.Event()
    box = {}

    def serve():
        asyncio.set_event_loop(loop)

        async def boot():
            runner = web.AppRunner(app)
            await runner.setup()
            site = web.TCPSite(runner, "127.0.0.1", port)
            await site.start()
            box["runner"] = runner

        try:
            loop.run_until_complete(boot())
        except Exception as exc:  # propagate bind/setup failures to the caller
            box["error"] = exc
            started.set()
            return
        started.set()
        loop.run_forever()
        # cooperative teardown once the caller stops the loop
        loop.run_until_complete(box["runner"].cleanup())
        loop.close()

    thread = threading.Thread(target=serve, daemon=True)
    thread.start()
    if not started.wait(30):
        raise RuntimeError("HTTP bench server did not start within 30s")
    if "error" in box:
        raise RuntimeError("HTTP bench server failed to start") from box["error"]

    def stop():
        loop.call_soon_threadsafe(loop.stop)
        thread.join(timeout=30)

    return port, stop


def _post_json(port: int, path: str, payload: bytes, timeout: float = 30.0):
    import urllib.request

    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=payload,
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as response:
        response.read()


def bench_http(iters: int = 200):
    """End-to-end HTTP p50/p99 against the real aiohttp server: boots the server in
    this process on a free port, drives single-row POST /predict requests, and tears
    the runner/loop/thread down afterwards."""
    import json as _json

    from unionml_tpu.model import ModelArtifact
    from unionml_tpu.serving import build_aiohttp_app

    model, feature_names = _build_mlp_model("http_bench_model")
    model.artifact = ModelArtifact(model._init_model_object({}), None, None)

    port, stop = _serve_app(build_aiohttp_app(model))
    payload = _json.dumps(
        {"features": [dict(zip(feature_names, np.random.default_rng(1).normal(size=64)))]}
    ).encode()
    try:
        stats = _measure(lambda: _post_json(port, "/predict", payload), iters=iters)
        stats["http_p50_ms"] = stats["p50_ms"]  # explicit: this entry IS end-to-end HTTP
        # the server's own device-side split, via the /stats endpoint it serves
        import urllib.request

        with urllib.request.urlopen(f"http://127.0.0.1:{port}/stats", timeout=10) as resp:
            server_stats = _json.loads(resp.read())
        stats.update(
            {k: v for k, v in server_stats.get("device_latency", {}).items() if k != "count"}
        )
        return stats
    finally:
        stop()


def _serving_mesh(n_devices: int, num_heads: int):
    """A {data, tensor} serving mesh over the first ``n_devices`` devices, the
    tensor axis as wide as the head count divides (KV shards over heads)."""
    import jax

    from unionml_tpu.parallel import make_mesh

    tensor = 1
    for cand in (8, 4, 2):
        if cand <= n_devices and num_heads % cand == 0 and n_devices % cand == 0:
            tensor = cand
            break
    return make_mesh(
        {"data": n_devices // tensor, "tensor": tensor}, devices=jax.devices()[:n_devices]
    )


def _bench_gpt():
    """The decoder every generation bench serves (tiny on CPU, GPT-2 small on TPU)."""
    import jax
    import jax.numpy as jnp

    from unionml_tpu.models import GPTConfig, GPTLMHeadModel
    from unionml_tpu.models.gpt import init_params

    if jax.default_backend() == "cpu":
        config = GPTConfig.tiny(dropout=0.0, dtype=jnp.float32, attention_impl="xla")
    else:  # GPT-2 small on a real accelerator
        config = GPTConfig(dropout=0.0, dtype=jnp.bfloat16)
    model = GPTLMHeadModel(config)
    variables = init_params(config, seq_len=16)
    return config, model, variables


def bench_generate(iters: int = 30, max_new_tokens: int = 16, concurrency: int = 8,
                   lookahead: int = 8, mesh_devices: int = 0):
    """Continuous-batching /generate over real HTTP: per-completion latency plus
    aggregate decode throughput under concurrent load (the continuous-batching
    payoff: N concurrent requests share every decode step).

    ``mesh_devices=N`` serves the SHARDED engine (params Megatron-split, KV cache
    sharded over heads) across an N-device {data, tensor} mesh — the multi-chip
    serving path, same HTTP surface."""
    import json as _json
    import threading
    import types

    config, model, variables = _bench_gpt()
    mesh = _serving_mesh(mesh_devices, config.num_heads) if mesh_devices else None

    from unionml_tpu.serving import build_aiohttp_app
    from unionml_tpu.serving.continuous import DecodeEngine

    stub = types.SimpleNamespace(name="generate_bench_model", artifact=object())

    port, stop = _serve_app(
        build_aiohttp_app(
            stub, resident=False, coalesce=False,
            generator=lambda: DecodeEngine(
                model, variables, num_slots=concurrency, max_len=128,
                prefill_buckets=(8, 16), mesh=mesh,
            ),
            # fuse decode steps per device dispatch: cuts per-token host syncs
            # (the dominant cost on remote devices; measurable device-local too)
            generate_lookahead=lookahead,
        )
    )
    payload = _json.dumps({"prompt_ids": [3, 1, 4, 1, 5], "max_new_tokens": max_new_tokens}).encode()

    def request():
        _post_json(port, "/generate", payload, timeout=120)

    try:
        stats = _measure(request, iters=iters)
        stats["max_new_tokens"] = max_new_tokens
        stats["tokens_per_s_single"] = round(max_new_tokens / (stats["p50_ms"] / 1e3), 1)

        # concurrent phase: `concurrency` client threads sharing the engine's slots
        request()  # ensure every bucket is warm before the timed burst
        n_each = max(1, iters // concurrency)
        t0 = time.perf_counter()
        threads = [
            threading.Thread(target=lambda: [request() for _ in range(n_each)])
            for _ in range(concurrency)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        elapsed = time.perf_counter() - t0
        total_tokens = concurrency * n_each * max_new_tokens
        stats["concurrency"] = concurrency
        stats["lookahead"] = lookahead
        stats["mesh_devices"] = mesh_devices or 1
        stats["tokens_per_s_concurrent"] = round(total_tokens / elapsed, 1)
        return stats
    finally:
        stop()


def bench_prefill_mix(n_prompts: int = 16, prompt_len: int = 48, max_new_tokens: int = 4,
                      prefill_batch: int = 4, mesh_devices: int = 0):
    """Prefill-heavy mix: N long-prompt/short-completion requests queued at once.

    The admission-bottleneck scenario from serving/continuous.py — prompt-heavy
    load used to serialize one prefill dispatch per prompt. Measures the batched
    path (⌈N/prefill_batch⌉ dispatches) against the serial one (prefill_batch=1)
    on the SAME engine config, engine-level for a clean device-dispatch count
    (no HTTP jitter in a number meant for hardware-window comparison).
    """
    config, model, variables = _bench_gpt()
    mesh = _serving_mesh(mesh_devices, config.num_heads) if mesh_devices else None

    from unionml_tpu.serving.continuous import DecodeEngine

    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, config.vocab_size, size=prompt_len).tolist() for _ in range(n_prompts)]
    requests = [(p, max_new_tokens) for p in prompts]
    bucket = 1 << (prompt_len - 1).bit_length()

    def run(batch_size):
        engine = DecodeEngine(
            model, variables, num_slots=n_prompts, max_len=2 * bucket,
            prefill_buckets=(bucket,), prefill_batch=batch_size, mesh=mesh,
        )
        # warm the (batch_size, bucket) prefill/insert/decode programs so the
        # timed admission measures dispatches, not XLA compiles
        engine.admit_many(requests[:batch_size])
        while engine.num_active:
            engine.step()
        warm_dispatches = engine.prefill_dispatches
        with _RetraceCounter() as retraces:
            t0 = time.perf_counter()
            slots = engine.admit_many(requests)
            admit_s = time.perf_counter() - t0
            while engine.num_active:
                engine.step()
            total_s = time.perf_counter() - t0
        return {
            "admit_s": round(admit_s, 4),
            "total_s": round(total_s, 4),
            "prefill_dispatches": engine.prefill_dispatches - warm_dispatches,
            "retraces": retraces.count,
            "prompts_per_s_admission": round(len(slots) / admit_s, 1),
        }

    batched = run(prefill_batch)
    serial = run(1)
    return {
        "n_prompts": n_prompts,
        "prompt_len": prompt_len,
        "max_new_tokens": max_new_tokens,
        "prefill_batch": prefill_batch,
        "mesh_devices": mesh_devices or 1,
        "batched": batched,
        "serial": serial,
        "admission_speedup": round(serial["admit_s"] / batched["admit_s"], 2)
        if batched["admit_s"] else None,
    }


def bench_prefix_heavy(n_requests: int = 0, shared_len: int = 0, suffix_len: int = 0,
                       max_new_tokens: int = 4, block_size: int = 0,
                       cache_blocks: int = 0, mesh_devices: int = 0):
    """Prefix-heavy mix: N requests sharing a K-token prefix (system prompt /
    few-shot template traffic), cache-ON vs cache-OFF on the same engine config.

    The prefix-cache payoff is FLOPs, not dispatches: every follower restores
    the shared prefix's KV from the block pool (one shard-local gather) and
    prefills only its unique suffix. Reported per run: prefill tokens
    recomputed, prefill dispatches, restore/save copies, cache hit rate, and
    admission wall time — engine-level, like the prefill mix, so the
    hardware-window numbers carry no HTTP jitter. Requests admit in waves of
    ``num_slots`` (the queued-traffic shape): wave 1 seeds the cache, later
    waves hit.

    Zero-valued size params pick backend defaults: the acceptance-scale
    100 x (512 shared + 64 suffix) workload on an accelerator, a scaled-down
    16 x (48 + 8) on CPU (the tiny config's 128-position budget).
    """
    import jax

    from unionml_tpu.serving.continuous import DecodeEngine

    config, model, variables = _bench_gpt()
    mesh = _serving_mesh(mesh_devices, config.num_heads) if mesh_devices else None
    on_cpu = jax.default_backend() == "cpu"
    n_requests = n_requests or (16 if on_cpu else 100)
    shared_len = shared_len or (48 if on_cpu else 512)
    suffix_len = suffix_len or (8 if on_cpu else 64)
    block_size = block_size or (8 if on_cpu else 32)
    prompt_len = shared_len + suffix_len
    # default pool: the shared prefix + every request's unique tail (plus warmup
    # slack) fits without eviction churn — the steady-state sizing a server
    # would pick for its system-prompt working set
    cache_blocks = cache_blocks or (
        prompt_len // block_size + 1 + (n_requests + 4) * (suffix_len // block_size + 1)
    )
    bucket = 1 << (prompt_len - 1).bit_length()
    suffix_bucket = 1 << (suffix_len - 1).bit_length()
    max_len = min(config.max_position_embeddings, bucket + 2 * max_new_tokens + suffix_bucket)

    rng = np.random.default_rng(0)
    shared = rng.integers(1, config.vocab_size, size=shared_len)
    prompts = [
        np.concatenate([shared, rng.integers(1, config.vocab_size, size=suffix_len)]).tolist()
        for _ in range(n_requests)
    ]
    num_slots = min(8, n_requests)

    def run(blocks):
        engine = DecodeEngine(
            model, variables, num_slots=num_slots, max_len=max_len,
            prefill_buckets=(suffix_bucket, bucket), prefill_batch=4, mesh=mesh,
            prefix_cache_blocks=blocks, prefix_block_size=block_size,
        )
        # warm every compiled program (prefill, suffix chunk, restore/save,
        # insert, decode) so the timed waves measure dispatches, not compiles
        warm = [rng.integers(1, config.vocab_size, size=prompt_len).tolist()
                for _ in range(2)]
        for p in warm:
            engine.generate(p, max_new_tokens)
        base_tokens = engine.prefill_tokens_computed
        base_dispatches = engine.prefill_dispatches
        pending = list(prompts)
        t0 = time.perf_counter()
        while pending or engine.num_active or engine.has_pending_prefill:
            free = len(engine.free_slots)
            if pending and free:
                wave, pending = pending[:free], pending[free:]
                engine.admit_many([(p, max_new_tokens) for p in wave])
            engine.step()
        total_s = time.perf_counter() - t0
        out = {
            "total_s": round(total_s, 4),
            "prefill_tokens_computed": engine.prefill_tokens_computed - base_tokens,
            "prefill_dispatches": engine.prefill_dispatches - base_dispatches,
        }
        if engine.prefix_cache is not None:
            stats = engine.prefix_cache.stats()
            out["hit_rate"] = round(stats["hits"] / max(stats["lookups"], 1), 3)
            out["hit_tokens"] = stats["hit_tokens"]
            out["evicted_blocks"] = stats["evicted_blocks"]
            out["restore_dispatches"] = engine.prefix_restore_dispatches
            out["save_dispatches"] = engine.prefix_save_dispatches
        return out

    cached = run(cache_blocks)
    uncached = run(0)
    return {
        "n_requests": n_requests,
        "shared_len": shared_len,
        "suffix_len": suffix_len,
        "block_size": block_size,
        "cache_blocks": cache_blocks,
        "max_new_tokens": max_new_tokens,
        "mesh_devices": mesh_devices or 1,
        "cached": cached,
        "uncached": uncached,
        "prefill_tokens_saved_frac": round(
            1 - cached["prefill_tokens_computed"] / max(uncached["prefill_tokens_computed"], 1), 4
        ),
        "speedup_total": round(uncached["total_s"] / cached["total_s"], 2)
        if cached["total_s"] else None,
    }


def bench_pipeline(modes=("on", "off"), n_requests: int = 8, max_new_tokens: int = 64,
                   mesh_devices: int = 0):
    """Depth-1 pipelined decode A/B: dispatch-ahead ON vs OFF, same engine
    config and workload (``bench_serving.py --pipeline {on,off,ab}``).

    The pipelining payoff is the HOST GAP: with pipelining off the device
    idles from each token fetch until the host has applied tokens, admitted
    requests, and dispatched the next step; with depth-1 dispatch-ahead the
    next step is already queued when the host starts that work, so the gap
    collapses to ~0. Reported per mode, from the engine's phase counters over
    the timed run: decode tok/s, ``fetch_wait_ms`` (host time blocked in the
    token fetch, per fetch), ``host_ms_per_step`` (every other phase's time per
    dispatch: what an unpipelined device waits for), and the idle-dispatch
    fraction — engine-level (no HTTP jitter), lookahead=1 (the latency-serving
    shape where the per-tick host sync dominates).
    """
    config, model, variables = _bench_gpt()
    mesh = _serving_mesh(mesh_devices, config.num_heads) if mesh_devices else None

    from unionml_tpu.serving.continuous import DecodeEngine

    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, config.vocab_size, size=6).tolist() for _ in range(n_requests)]

    def run(pipelined: bool):
        engine = DecodeEngine(
            model, variables, num_slots=min(8, n_requests), max_len=128,
            prefill_buckets=(8,), mesh=mesh, pipeline=pipelined,
        )
        engine.generate(prompts[0], 4)  # warm the prefill/decode programs
        # warmup out of the books: the counters only grow, so the timed run
        # is the difference of two reads
        before = engine.pipeline_stats()
        base_tokens = engine.tokens_decoded
        pending = list(prompts)
        # retrace counter over the TIMED window: correlates graftlint retrace
        # findings with a measured number — a clean steady state reports the
        # (bounded) admission-shape compiles and nothing per-step
        with _RetraceCounter() as retraces:
            t0 = time.perf_counter()
            while pending or engine.num_active or engine.has_pending_events:
                free = len(engine.free_slots)
                if pending and free:
                    wave, pending = pending[:free], pending[free:]
                    engine.admit_many([(p, max_new_tokens) for p in wave])
                engine.step()
            elapsed = time.perf_counter() - t0
        decoded = engine.tokens_decoded - base_tokens
        after = engine.pipeline_stats()
        delta = {
            phase: {k: after["phases"][phase][k] - before["phases"][phase][k]
                    for k in ("seconds", "entries")}
            for phase in after["phases"]
        }
        steps = max(after["step_dispatches"] - before["step_dispatches"], 1)
        host_s = sum(d["seconds"] for phase, d in delta.items() if phase not in ("idle", "fetch_wait"))
        return {
            "decode_tok_s": round(decoded / elapsed, 1),
            "total_s": round(elapsed, 4),
            "tokens": decoded,
            "retraces": retraces.count,
            "fetch_wait_ms": round(
                1e3 * delta["fetch_wait"]["seconds"] / max(delta["fetch_wait"]["entries"], 1), 3
            ),
            "host_ms_per_step": round(1e3 * host_s / steps, 3),
            "idle_dispatch_frac": round(
                (after["idle_dispatches"] - before["idle_dispatches"]) / steps, 3
            ),
        }

    out = {
        "n_requests": n_requests,
        "max_new_tokens": max_new_tokens,
        "lookahead": 1,
        "mesh_devices": mesh_devices or 1,
    }
    for mode in modes:
        out["pipeline_" + mode] = run(mode == "on")
    if "pipeline_on" in out and "pipeline_off" in out:
        out["idle_dispatch_frac_reduction"] = round(
            out["pipeline_off"]["idle_dispatch_frac"] - out["pipeline_on"]["idle_dispatch_frac"], 3
        )
        out["speedup_tok_s"] = round(
            out["pipeline_on"]["decode_tok_s"]
            / max(out["pipeline_off"]["decode_tok_s"], 1e-9),
            3,
        )
    return out


def bench_paged(modes=("on", "off"), n_requests: int = 16, prompt_len: int = 6,
                max_new_tokens: int = 24, mesh_devices: int = 0):
    """Paged-vs-dense KV A/B at EQUAL KV byte budget
    (``bench_serving.py --paged {on,off,ab}``).

    Both arms get exactly 256 cached token positions of KV: dense reserves
    them as 4 rigid ``max_len=64`` slot rows, so 4 requests decode
    concurrently no matter how short they are; paged pools them as 64
    four-token blocks (65 with the scratch block) behind a block table, so a
    request only holds ``ceil((len+budget)/4)`` blocks and short requests
    pack the same bytes 2x+ deeper. Reported per arm: measured PEAK
    concurrency, decode tok/s, wall time, and the per-request-footprint
    slots-vs-memory curve (concurrent requests each arm fits at this byte
    budget, by request length). The ``ab`` mode gates: paged must fit
    >= 1.5x the concurrent requests AND the two arms' greedy streams must
    be token-identical, else the battery step fails."""
    config, model, variables = _bench_gpt()
    mesh = _serving_mesh(mesh_devices, config.num_heads) if mesh_devices else None

    from unionml_tpu.serving.continuous import DecodeEngine

    BS, MAX_LEN, KV_TOKENS = 4, 64, 256  # the shared byte budget, in positions
    rng = np.random.default_rng(0)
    prompts = [
        rng.integers(1, config.vocab_size, size=prompt_len).tolist()
        for _ in range(n_requests)
    ]

    def run(paged: bool):
        if paged:
            # 64 usable blocks + the scratch block: 256 positions, same bytes
            engine = DecodeEngine(
                model, variables, num_slots=16, max_len=MAX_LEN,
                prefill_buckets=(8,), mesh=mesh, paged=True,
                pool_blocks=KV_TOKENS // BS + 1, prefix_block_size=BS,
                prefix_cache_blocks=0,
            )
        else:
            engine = DecodeEngine(
                model, variables, num_slots=KV_TOKENS // MAX_LEN, max_len=MAX_LEN,
                prefill_buckets=(8,), mesh=mesh, paged=False,
            )
        engine.generate(prompts[0], 4)  # warm the prefill/decode programs
        base_tokens = engine.tokens_decoded
        pending = list(enumerate(prompts))
        streams = {i: [] for i in range(n_requests)}
        req_of_slot = {}
        peak = 0
        with _RetraceCounter() as retraces:
            t0 = time.perf_counter()
            while pending or engine.num_active or engine.has_pending_events:
                while pending and engine.free_slots:
                    i, p = pending[0]
                    avail = engine.available_blocks()
                    if avail is not None and engine.block_demand(len(p), max_new_tokens) > avail:
                        break  # block-gated (the batcher's admission rule)
                    pending.pop(0)
                    (slot,) = engine.admit_many([(p, max_new_tokens)])
                    req_of_slot[slot] = i
                peak = max(peak, engine.num_active)
                for ev in engine.step():
                    if ev.emit:
                        streams[req_of_slot[ev.slot]].append(ev.token)
            elapsed = time.perf_counter() - t0
        decoded = engine.tokens_decoded - base_tokens
        return {
            "decode_tok_s": round(decoded / elapsed, 1),
            "total_s": round(elapsed, 4),
            "tokens": decoded,
            "retraces": retraces.count,
            "peak_concurrent": peak,
            "kv_token_budget": KV_TOKENS,
        }, streams

    footprint = prompt_len + max_new_tokens
    out = {
        "n_requests": n_requests,
        "prompt_len": prompt_len,
        "max_new_tokens": max_new_tokens,
        "request_kv_footprint": footprint,
        "mesh_devices": mesh_devices or 1,
        # the slots-vs-memory curve: concurrent requests each arm fits into
        # the SAME 256 cached positions, by per-request KV footprint
        "slots_vs_memory": {
            str(length): {
                "dense_concurrent": KV_TOKENS // MAX_LEN,
                "paged_concurrent": (KV_TOKENS // BS) // -(-length // BS),
            }
            for length in (8, 16, 32, 64)
        },
    }
    streams_by_mode = {}
    for mode in modes:
        entry, streams = run(mode == "on")
        out["paged_" + mode] = entry
        streams_by_mode[mode] = streams
    if "paged_on" in out and "paged_off" in out:
        out["concurrency_ratio"] = round(
            out["paged_on"]["peak_concurrent"]
            / max(out["paged_off"]["peak_concurrent"], 1), 3
        )
        out["speedup_tok_s"] = round(
            out["paged_on"]["decode_tok_s"]
            / max(out["paged_off"]["decode_tok_s"], 1e-9), 3
        )
        out["token_identical"] = streams_by_mode["on"] == streams_by_mode["off"]
    return out


def bench_int8_kv(modes=("on", "off"), n_requests: int = 16, prompt_len: int = 6,
                  max_new_tokens: int = 24, mesh_devices: int = 0):
    """int8-vs-bf16 KV POOL A/B at EQUAL pool byte budget
    (``bench_serving.py --int8 {on,off,ab}``).

    Both arms are paged and get the SAME pool bytes: the bf16 arm keeps the
    PR-11 geometry (65 four-token blocks behind block tables), the int8 arm
    converts that byte budget into int8 blocks via ``gpt.kv_block_bytes`` —
    int8 payload + per-(block, head) f32 scales per block, so the same HBM
    holds ~2x the cached positions (~3.8x on the f32 CPU harness). Reported
    per arm: measured PEAK concurrency under block-gated admission, decode
    tok/s, and the pool's stored-vs-dense-equivalent bytes from
    ``kv_pool_stats()``. The ``ab`` mode gates BOTH halves of the tentpole
    claim in one run: int8 must fit >= 1.8x the concurrent requests at equal
    bytes AND a greedy logit probe (pipeline=False engines, per-step
    ``_last_logits``) must stay within the pinned quality budgets
    ``KV_INT8_LOGPROB_DELTA_BUDGET`` / ``KV_INT8_GREEDY_DIVERGENCE_BUDGET``,
    else the battery step fails."""
    from unionml_tpu.models.gpt import kv_block_bytes
    from unionml_tpu.ops.quant import (
        KV_INT8_GREEDY_DIVERGENCE_BUDGET,
        KV_INT8_LOGPROB_DELTA_BUDGET,
    )
    from unionml_tpu.serving.continuous import DecodeEngine

    config, model, variables = _bench_gpt()
    mesh = _serving_mesh(mesh_devices, config.num_heads) if mesh_devices else None

    BS, MAX_LEN, KV_TOKENS = 4, 64, 256
    dense_blocks = KV_TOKENS // BS + 1  # PR-11 pool: 64 usable + scratch
    bytes_dense = kv_block_bytes(config, BS)
    bytes_int8 = kv_block_bytes(config, BS, kv_quantize="int8")
    pool_byte_budget = dense_blocks * bytes_dense
    int8_blocks = pool_byte_budget // bytes_int8  # same bytes, more blocks
    rng = np.random.default_rng(0)
    prompts = [
        rng.integers(1, config.vocab_size, size=prompt_len).tolist()
        for _ in range(n_requests)
    ]

    def run(int8: bool):
        engine = DecodeEngine(
            model, variables, num_slots=16, max_len=MAX_LEN,
            prefill_buckets=(8,), mesh=mesh, paged=True,
            pool_blocks=int8_blocks if int8 else dense_blocks,
            prefix_block_size=BS, prefix_cache_blocks=0,
            kv_quantize="int8" if int8 else None,
        )
        engine.generate(prompts[0], 4)  # warm the prefill/decode programs
        base_tokens = engine.tokens_decoded
        pending = list(prompts)
        peak = 0
        with _RetraceCounter() as retraces:
            t0 = time.perf_counter()
            while pending or engine.num_active or engine.has_pending_events:
                while pending and engine.free_slots:
                    avail = engine.available_blocks()
                    if (avail is not None
                            and engine.block_demand(len(pending[0]), max_new_tokens) > avail):
                        break  # block-gated (the batcher's admission rule)
                    engine.admit_many([(pending.pop(0), max_new_tokens)])
                peak = max(peak, engine.num_active)
                engine.step()
            elapsed = time.perf_counter() - t0
        decoded = engine.tokens_decoded - base_tokens
        stats = engine.kv_pool_stats()
        return {
            "decode_tok_s": round(decoded / elapsed, 1),
            "total_s": round(elapsed, 4),
            "tokens": decoded,
            "retraces": retraces.count,
            "peak_concurrent": peak,
            "pool_blocks": int8_blocks if int8 else dense_blocks,
            "kv_dtype": stats["kv_dtype"],
            "kv_pool_bytes": stats["kv_pool_bytes"],
            "kv_pool_bytes_dense_equiv": stats["kv_pool_bytes_dense_equiv"],
        }

    def logsoftmax(x):
        x = x - x.max()
        return x - np.log(np.exp(x).sum())

    def greedy_trace(engine, prompt, n):
        # pipeline=False keeps _last_logits as "the logits token t samples from"
        slot = engine.add_request(list(prompt), n)
        toks, logits = [], []
        for _ in range(n):
            logits.append(np.asarray(engine._last_logits)[slot].copy())
            toks.extend(ev.token for ev in engine.step() if ev.emit and ev.slot == slot)
        while engine.busy or engine._inflight is not None or engine.has_pending_events:
            engine.step()
        return toks, logits

    def quality_probe():
        """The pinned quality gate, run against the SAME budgets the unit
        tests pin: greedy-divergence rate and pre-divergence logprob delta
        of the int8 pool vs the bf16 pool."""
        kw = dict(num_slots=4, max_len=MAX_LEN, prefill_buckets=(8,), mesh=mesh,
                  paged=True, pool_blocks=dense_blocks, prefix_block_size=BS,
                  prefix_cache_blocks=0, pipeline=False, prefill_chunk=None)
        ref = DecodeEngine(model, variables, **kw)
        quant = DecodeEngine(model, variables, kv_quantize="int8", **kw)
        probe_rng = np.random.default_rng(1)
        probes = [probe_rng.integers(1, config.vocab_size, size=8).tolist()
                  for _ in range(3)]
        total = diverged = 0
        max_delta = 0.0
        for prompt in probes:
            t_ref, l_ref = greedy_trace(ref, prompt, 16)
            t_q, l_q = greedy_trace(quant, prompt, 16)
            m = min(len(t_ref), len(t_q))
            first = next((i for i in range(m) if t_ref[i] != t_q[i]), m)
            total += m
            diverged += m - first
            for i in range(first):  # only the common prefix is comparable
                delta = abs(logsoftmax(l_ref[i])[t_ref[i]] - logsoftmax(l_q[i])[t_ref[i]])
                max_delta = max(max_delta, float(delta))
        rate = diverged / max(total, 1)
        return {
            "probe_tokens": total,
            "divergence_rate": round(rate, 4),
            "divergence_budget": KV_INT8_GREEDY_DIVERGENCE_BUDGET,
            "max_logprob_delta": round(max_delta, 4),
            "logprob_delta_budget": KV_INT8_LOGPROB_DELTA_BUDGET,
            "quality_ok": bool(
                total > 0
                and rate <= KV_INT8_GREEDY_DIVERGENCE_BUDGET
                and max_delta <= KV_INT8_LOGPROB_DELTA_BUDGET
            ),
        }

    out = {
        "n_requests": n_requests,
        "prompt_len": prompt_len,
        "max_new_tokens": max_new_tokens,
        "request_kv_footprint": prompt_len + max_new_tokens,
        "mesh_devices": mesh_devices or 1,
        "pool_byte_budget": pool_byte_budget,
        "kv_block_bytes_dense": bytes_dense,
        "kv_block_bytes_int8": bytes_int8,
        "blocks_per_byte_ratio": round(bytes_dense / bytes_int8, 3),
    }
    for mode in modes:
        out["int8_" + mode] = run(mode == "on")
    if "int8_on" in out and "int8_off" in out:
        out["concurrency_ratio"] = round(
            out["int8_on"]["peak_concurrent"]
            / max(out["int8_off"]["peak_concurrent"], 1), 3
        )
        out["speedup_tok_s"] = round(
            out["int8_on"]["decode_tok_s"]
            / max(out["int8_off"]["decode_tok_s"], 1e-9), 3
        )
        out["quality"] = quality_probe()
    return out


def bench_obs(modes=("on", "off"), n_requests: int = 16, max_new_tokens: int = 32,
              repeats: int = 3, mesh_devices: int = 0):
    """Telemetry ON-vs-OFF A/B: the same concurrent request mix through the
    asyncio batcher with the span/metrics subsystem attached vs absent
    (``bench_serving.py --obs {on,off,ab}``).

    The telemetry contract is "zero new host↔device syncs, one host branch
    per hook when disabled": decode timing piggybacks on the fused deferred
    fetch's existing stamps, and every recording site is lock-leaf host
    arithmetic. This phase puts a number on that claim — best-of-``repeats``
    decode tok/s per arm (best-of because the CPU smoke arm is scheduler-
    noisy; a real regression shifts the best, noise only shifts the mean) —
    and the ``ab`` entry point GATES at 2%: enabled throughput below 0.98×
    disabled fails the battery step.
    """
    import asyncio

    config, model, variables = _bench_gpt()
    mesh = _serving_mesh(mesh_devices, config.num_heads) if mesh_devices else None

    from unionml_tpu.serving.continuous import ContinuousBatcher, DecodeEngine
    from unionml_tpu.serving.telemetry import Telemetry

    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, config.vocab_size, size=6).tolist() for _ in range(n_requests)]

    def run_once(enabled: bool):
        telemetry = Telemetry() if enabled else None
        engine = DecodeEngine(
            model, variables, num_slots=min(8, n_requests), max_len=128,
            prefill_buckets=(8,), mesh=mesh,
        )
        batcher = ContinuousBatcher(engine, telemetry=telemetry)

        async def drive():
            await batcher.generate(prompts[0], 4)  # warm the prefill/decode programs
            base = engine.tokens_decoded
            t0 = time.perf_counter()
            await asyncio.gather(
                *(batcher.generate(p, max_new_tokens) for p in prompts)
            )
            elapsed = time.perf_counter() - t0
            return engine.tokens_decoded - base, elapsed

        try:
            decoded, elapsed = asyncio.run(drive())
        finally:
            batcher.close()
        entry = {
            "decode_tok_s": round(decoded / elapsed, 1),
            "total_s": round(elapsed, 4),
            "tokens": decoded,
        }
        if telemetry is not None:
            tstats = telemetry.stats()
            entry["traces_completed"] = tstats["completed_traces"]
            entry["spans_dropped"] = tstats["spans_dropped"]
            # spans per trace: the per-request record cost the ring amortizes
            traces = telemetry.recent(n_requests + 1)
            entry["spans_per_trace"] = round(
                sum(len(t["spans"]) for t in traces) / max(len(traces), 1), 1
            )
        return entry

    out = {
        "n_requests": n_requests,
        "max_new_tokens": max_new_tokens,
        "repeats": repeats,
        "mesh_devices": mesh_devices or 1,
    }
    for mode in modes:
        runs = [run_once(mode == "on") for _ in range(repeats)]
        best = max(runs, key=lambda r: r["decode_tok_s"])
        out["obs_" + mode] = dict(best, runs_tok_s=[r["decode_tok_s"] for r in runs])
    if "obs_on" in out and "obs_off" in out:
        on_best = out["obs_on"]["decode_tok_s"]
        off_best = out["obs_off"]["decode_tok_s"]
        out["overhead_frac"] = round(1.0 - on_best / max(off_best, 1e-9), 4)
    return out


def bench_slo_mix(n_batch: int = 24, n_interactive: int = 8, num_slots: int = 4,
                  batch_tokens: int = 48, interactive_tokens: int = 8,
                  interactive_deadline_ms: float = 30_000.0, mesh_devices: int = 0):
    """Mixed SLO workload: interactive (high priority, deadline) requests
    arriving into a queue already flooded with batch work — the saturation
    shape where the SCHEDULER, not the step function, sets tail latency.

    A/B: the SLO scheduler (priority classes + aging + preempt-to-prefix-
    cache) vs the same batcher in FIFO mode (arrival order, no preemption —
    the pre-scheduler behavior). Reported per arm and per class: TTFT
    p50/p95/p99 and inter-token latency percentiles (client-side, engine-level
    over the asyncio batcher — no HTTP jitter), plus shed / preemption /
    deadline-miss counters and the queue-wait EMA. The acceptance signal is
    interactive-class p95 TTFT: FIFO makes an interactive arrival drain the
    whole batch backlog first; the scheduler pops it to the front and, with no
    free slot, preempts a batch victim into the prefix cache.
    """
    import asyncio
    import contextlib

    from unionml_tpu.serving.continuous import ContinuousBatcher, DecodeEngine
    from unionml_tpu.serving.scheduler import SchedulerConfig, SchedulingError, SLOScheduler

    config, model, variables = _bench_gpt()
    mesh = _serving_mesh(mesh_devices, config.num_heads) if mesh_devices else None
    rng = np.random.default_rng(0)
    batch_prompts = [rng.integers(1, config.vocab_size, size=6).tolist() for _ in range(n_batch)]
    inter_prompts = [rng.integers(1, config.vocab_size, size=6).tolist() for _ in range(n_interactive)]

    def pct(xs):
        if not xs:
            return None
        xs = sorted(xs)
        pick = lambda q: round(xs[min(int(len(xs) * q), len(xs) - 1)], 2)
        return {"p50_ms": pick(0.5), "p95_ms": pick(0.95), "p99_ms": pick(0.99)}

    def warm(engine, fifo: bool):
        """Warm every program the timed window can hit, so TTFT measures
        SCHEDULING, not XLA compiles: the multi-row bucket prefill, the decode
        step, and — scheduler arm only — the preempt-to-prefix-cache ladder
        (restore / block-save / suffix-prefill compile once per
        transcript-block-count shape)."""
        warm_rng = np.random.default_rng(1)
        for rows in range(1, num_slots + 1):
            # admission pops 1..num_slots requests per wave: every (rows,
            # bucket) prefill shape can appear in the timed window
            prompts = [warm_rng.integers(1, config.vocab_size, size=6).tolist()
                       for _ in range(rows)]
            engine.admit_many([(p, 2) for p in prompts])
            while engine.num_active:
                engine.step()
        if fifo:
            return
        for steps in range(4, batch_tokens, 8):
            prompt = warm_rng.integers(1, config.vocab_size, size=6).tolist()
            slot = engine.add_request(prompt, batch_tokens + 1)
            for _ in range(steps):
                engine.step()
            state = engine.preempt(slot)
            if state is None:
                continue
            engine.add_request(state.tokens, batch_tokens + 1 - (len(state.tokens) - 6))
            engine.release_preempted(state)
            while engine.num_active:
                engine.step()

    def run(fifo: bool):
        engine = DecodeEngine(
            model, variables, num_slots=num_slots, max_len=128, prefill_buckets=(8,),
            mesh=mesh, prefix_cache_blocks=128, prefix_block_size=8,
        )
        warm(engine, fifo)
        scheduler = SLOScheduler(
            SchedulerConfig(fifo=fifo, preempt=not fifo, max_queue=4096)
        )
        batcher = ContinuousBatcher(engine, scheduler=scheduler)
        ttft = {"interactive": [], "batch": []}
        itl = {"interactive": [], "batch": []}
        outcomes = {"completed": 0, "shed": 0, "deadline_missed": 0}

        async def one(cls, prompt, n, deadline_ms):
            loop = asyncio.get_running_loop()
            t0 = loop.time()
            last = None
            try:
                agen = batcher.stream(prompt, n, priority=cls, deadline_ms=deadline_ms)
                async with contextlib.aclosing(agen) as it:
                    async for _ in it:
                        now = loop.time()
                        if last is None:
                            ttft[cls].append((now - t0) * 1e3)
                        else:
                            itl[cls].append((now - last) * 1e3)
                        last = now
                outcomes["completed"] += 1
            except SchedulingError as exc:
                key = "deadline_missed" if exc.reason == "deadline_exceeded" else "shed"
                outcomes[key] += 1

        async def drive():
            t0 = time.perf_counter()
            tasks = [
                asyncio.ensure_future(one("batch", p, batch_tokens, None))
                for p in batch_prompts
            ]
            await asyncio.sleep(0.05)  # the batch flood owns the queue first
            for p in inter_prompts:  # interactive arrivals trickle in behind it
                tasks.append(
                    asyncio.ensure_future(
                        one("interactive", p, interactive_tokens, interactive_deadline_ms)
                    )
                )
                await asyncio.sleep(0.01)
            await asyncio.gather(*tasks)
            return time.perf_counter() - t0

        total_s = asyncio.run(drive())
        stats = scheduler.stats()
        batcher.close()
        return {
            "total_s": round(total_s, 4),
            "ttft_interactive": pct(ttft["interactive"]),
            "ttft_batch": pct(ttft["batch"]),
            "itl_interactive": pct(itl["interactive"]),
            "itl_batch": pct(itl["batch"]),
            "outcomes": outcomes,
            "queue_wait_ema_ms": stats["queue_wait_ema_ms"],
            "sheds": stats["shed_queue_full"] + stats["shed_deadline_infeasible"],
            "preemptions": stats["preemptions"],
            "deadline_misses": stats["deadline_misses_queued"] + stats["deadline_misses_running"],
        }

    scheduled = run(fifo=False)
    fifo = run(fifo=True)
    out = {
        "n_batch": n_batch,
        "n_interactive": n_interactive,
        "num_slots": num_slots,
        "batch_tokens": batch_tokens,
        "interactive_tokens": interactive_tokens,
        "interactive_deadline_ms": interactive_deadline_ms,
        "mesh_devices": mesh_devices or 1,
        "scheduler": scheduled,
        "fifo": fifo,
    }
    sp95 = (scheduled["ttft_interactive"] or {}).get("p95_ms")
    fp95 = (fifo["ttft_interactive"] or {}).get("p95_ms")
    if sp95 and fp95:
        out["interactive_p95_ttft_speedup"] = round(fp95 / sp95, 2)
    return out


def bench_chaos(n_requests: int = 8, max_new_tokens: int = 24, num_slots: int = 4,
                mesh_devices: int = 0):
    """Chaos smoke: recovery latency + recovered-token parity under injected
    engine failures (ISSUE 7's gate).

    A flood of requests runs twice on identically-seeded engines: once clean,
    once with a ``FaultPlan`` that kills a decode dispatch mid-flood and NaNs
    one slot's logits a little later. The supervised batcher must salvage the
    in-flight transcripts, rebuild, and resume — the report asserts what the
    chaos *suite* pins functionally, but MEASURED: how long a failure->ok
    transition takes wall-clock (``recovery_ms``), how many requests
    recovered vs died, and whether every recovered stream matched the clean
    run token-for-token (``parity``). The poisoned request must fail
    structured (reason ``nan_logits``), never hang."""
    import asyncio

    from unionml_tpu.serving.continuous import ContinuousBatcher, DecodeEngine
    from unionml_tpu.serving.faults import EngineFailure, FaultPlan
    from unionml_tpu.serving.supervisor import EngineSupervisor

    config, model, variables = _bench_gpt()
    mesh = _serving_mesh(mesh_devices, config.num_heads) if mesh_devices else None
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, config.vocab_size, size=6).tolist() for _ in range(n_requests)]

    def run(faults):
        engine = DecodeEngine(
            model, variables, num_slots=num_slots, max_len=128,
            # the ladder must hold a salvaged TRANSCRIPT (prompt + decoded
            # tokens), not just the prompts: resumes re-admit through it
            prefill_buckets=(8, 64),
            mesh=mesh, prefix_cache_blocks=128, prefix_block_size=8, faults=faults,
        )
        supervisor = EngineSupervisor(backoff_s=0.01, watchdog_interval_s=0.1)
        batcher = ContinuousBatcher(engine, supervisor=supervisor)

        async def drive():
            return await asyncio.gather(
                *(batcher.generate(p, max_new_tokens) for p in prompts),
                return_exceptions=True,
            )

        t0 = time.perf_counter()
        results = asyncio.run(drive())
        total_s = time.perf_counter() - t0
        stats = supervisor.stats()
        pinned = engine.prefix_cache.pinned_blocks
        batcher.close()
        return results, stats, total_s, pinned

    clean, _, clean_s, _ = run(None)
    plan = FaultPlan(step_dispatch_failures=(12,), nan_logits=((30, 1),))
    chaotic, stats, chaos_s, pinned = run(plan)

    recovered = failed = mismatched = hung = 0
    for want, got in zip(clean, chaotic):
        if isinstance(got, EngineFailure):
            failed += 1
        elif isinstance(got, Exception):
            hung += 1  # anything non-structured counts against the contract
        elif got == want:
            recovered += 1
        else:
            mismatched += 1
    return {
        "n_requests": n_requests,
        "max_new_tokens": max_new_tokens,
        "num_slots": num_slots,
        "mesh_devices": mesh_devices or 1,
        "faults_injected": plan.stats()["injected"],
        "recovered": recovered,
        "failed_structured": failed,
        "mismatched": mismatched,
        "unstructured_failures": hung,
        "parity": mismatched == 0 and hung == 0,
        "recovery_ms": stats["last_recovery_ms"],
        "rebuilds": stats["rebuilds"],
        "quarantines": failed,
        "pinned_blocks_leaked": pinned,
        "clean_total_s": round(clean_s, 4),
        "chaos_total_s": round(chaos_s, 4),
        "chaos_overhead_x": round(chaos_s / clean_s, 3) if clean_s else None,
    }


def bench_speculative(iters: int = 20, max_new_tokens: int = 32, gamma: int = 4):
    """Speculative vs plain single-stream /generate latency over real HTTP.

    The latency claim speculation makes — fewer target forwards per token when
    the draft's acceptance rate is high — measured end to end: same target
    model served twice, once behind the continuous engine (lookahead 1, honest
    single-stream baseline) and once behind ``SpeculativeBatcher``.
    """
    import json as _json
    import types

    import jax
    import jax.numpy as jnp

    from unionml_tpu.models import GPTConfig, GPTLMHeadModel
    from unionml_tpu.models.gpt import init_params
    from unionml_tpu.serving import SpeculativeBatcher, build_aiohttp_app
    from unionml_tpu.serving.continuous import DecodeEngine

    if jax.default_backend() == "cpu":
        t_cfg = GPTConfig.tiny(dropout=0.0, dtype=jnp.float32, attention_impl="xla")
        d_cfg = GPTConfig.tiny(
            dropout=0.0, dtype=jnp.float32, attention_impl="xla", num_layers=1
        )
    else:  # GPT-2 small target, 2-layer draft sharing the config family
        t_cfg = GPTConfig(dropout=0.0, dtype=jnp.bfloat16)
        d_cfg = GPTConfig(dropout=0.0, dtype=jnp.bfloat16, num_layers=2)
    target = GPTLMHeadModel(t_cfg)
    t_vars = init_params(t_cfg, seq_len=16)
    draft = GPTLMHeadModel(d_cfg)
    d_vars = init_params(d_cfg, seq_len=16)
    stub = types.SimpleNamespace(name="spec_bench_model", artifact=object())
    payload = _json.dumps({"prompt_ids": [3, 1, 4, 1, 5], "max_new_tokens": max_new_tokens}).encode()

    def measure(generator):
        port, stop = _serve_app(
            build_aiohttp_app(stub, resident=False, coalesce=False, generator=generator)
        )
        try:
            return _measure(lambda: _post_json(port, "/generate", payload, timeout=300), iters=iters)
        finally:
            stop()

    plain = measure(
        lambda: DecodeEngine(target, t_vars, num_slots=1, max_len=128, prefill_buckets=(8,))
    )
    spec = measure(SpeculativeBatcher(target, t_vars, draft, d_vars, gamma=gamma, max_len=128))
    return {
        "max_new_tokens": max_new_tokens,
        "gamma": gamma,
        "plain_p50_ms": plain["p50_ms"],
        "speculative_p50_ms": spec["p50_ms"],
        "speedup_p50": round(plain["p50_ms"] / spec["p50_ms"], 3) if spec["p50_ms"] else None,
        "iters": iters,
    }


def bench_spec(modes=("on", "off"), max_new_tokens: int = 32, mesh_devices: int = 0,
               train_steps: int = 120):
    """Adaptive speculative decoding A/B on the paged int8 pool
    (``bench_serving.py --spec {on,off,ab}``).

    Both arms are the SAME :class:`SpeculativeEngine` configuration — identical
    target+draft pools, so identical resident bytes by construction (the
    equal-pool-byte contract; ``kv_pool_stats`` charges the draft leaves too).
    The "off" arm admits every request with ``gamma=0``: zero proposals, one
    emitted token per round — vanilla decode run through the very same round
    program, which is what makes the identity gate BITWISE rather than
    approximate.

    Traffic is the SPECULATIVE_ANALYSIS.json recipe: a 4-layer char-GPT target
    and 1-layer draft trained on the same corpus, measured on two splits —
    in-distribution prompts (substrings of the training text, where the draft
    agrees and γ ramps) and adversarial held-out prompts (an unseen pangram
    plus uniform-random tokens, where acceptance collapses and γ must decay
    to 0 rather than lose to the baseline).

    The ``ab`` mode gates the tentpole's claim: in-distribution
    accepted-tokens-per-target-step >= 1.4 AND held-out >= 0.95 (adaptive γ
    never loses), with the on-arm streams token-identical to the off arm
    (greedy AND fixed-seed sampled) and the greedy streams identical to a
    PLAIN paged DecodeEngine at the same layout.
    """
    import jax
    import jax.numpy as jnp

    from unionml_tpu.models import GPTConfig, GPTLMHeadModel, create_train_state
    from unionml_tpu.models.training import fit_lm
    from unionml_tpu.serving.continuous import DecodeEngine
    from unionml_tpu.serving.speculative import SpeculativeEngine

    mesh = _serving_mesh(mesh_devices, 4) if mesh_devices else None
    vocab = 128
    text = (
        "the quick brown fox jumps over the lazy dog. "
        "pack my box with five dozen liquor jugs. "
        "how vexingly quick daft zebras jump. "
    ) * 80
    heldout_sentence = "sphinx of black quartz, judge my vow. "
    corpus = np.frombuffer(text.encode(), dtype=np.uint8).astype(np.int32) % vocab
    rng = np.random.default_rng(0)
    seqs = [
        corpus[i : i + int(n)]
        for i, n in zip(
            rng.integers(0, len(corpus) - 64, size=400), rng.integers(16, 64, size=400)
        )
    ]

    def train(num_layers: int):
        cfg = GPTConfig.tiny(
            vocab_size=vocab, hidden_size=64, num_layers=num_layers, num_heads=4,
            max_position_embeddings=128, dropout=0.0, dtype=jnp.float32,
            attention_impl="xla",
        )
        model = GPTLMHeadModel(cfg)
        variables = model.init(
            {"params": jax.random.PRNGKey(num_layers)}, jnp.zeros((1, 64), jnp.int32),
            deterministic=True,
        )
        state = create_train_state(model, variables, learning_rate=3e-3)
        result = fit_lm(
            state, seqs, seq_len=64, batch_size=32, num_steps=train_steps, pack=True,
            log_every=10_000,
        )
        return model, {"params": result.state.params}

    t0 = time.perf_counter()
    target, t_vars = train(4)
    draft, d_vars = train(1)
    train_s = time.perf_counter() - t0

    def encode(s):
        return [c % vocab for c in s.encode()]

    splits = {
        "in_distribution": [
            encode("the quick brown "), encode("pack my box "), encode("how vexingly "),
            encode("jumps over the "),
        ],
        "held_out": [
            encode(heldout_sentence[:16]), encode(heldout_sentence[7:23]),
            rng.integers(1, vocab, size=12).tolist(),  # adversarial: pure noise
            rng.integers(1, vocab, size=12).tolist(),
        ],
    }
    MAX_LEN = 128

    def make_engine(spec: bool):
        cls = SpeculativeEngine if spec else DecodeEngine
        kw = dict(
            num_slots=4, max_len=MAX_LEN, prefill_buckets=(16,), mesh=mesh,
            prefix_block_size=4, prefix_cache_blocks=64, kv_quantize="int8",
            seed=11, temperature=0.0,
        )
        if spec:
            return SpeculativeEngine(target, t_vars, draft, d_vars, **kw)
        return DecodeEngine(target, t_vars, paged=True, **kw)

    def drive(engine, reqs):
        streams, slot_req = {}, {}
        per_split = {}
        for split, prompt, rid, sampling in reqs:
            before = (
                engine.spec_accepted, engine.spec_slot_rounds, engine.spec_fallback_rounds,
            ) if isinstance(engine, SpeculativeEngine) else None
            (slot,) = engine.admit_many([(prompt, max_new_tokens, sampling)])
            slot_req[slot] = rid
            streams[rid] = []
            # one request at a time per split batch keeps the per-split
            # acceptance attribution exact (counters are engine-lifetime)
            while engine.num_active or engine.has_pending_prefill or engine.has_pending_events:
                for ev in engine.step(1):
                    if ev.emit:
                        streams[slot_req[ev.slot]].append(ev.token)
            if before is not None:
                acc = engine.spec_accepted - before[0]
                ran = (engine.spec_slot_rounds - before[1]) + (
                    engine.spec_fallback_rounds - before[2]
                )
                agg = per_split.setdefault(split, {"accepted": 0, "rounds": 0})
                agg["accepted"] += acc
                agg["rounds"] += ran
        return streams, per_split

    def requests(sampling_extra):
        reqs, rid = [], 0
        for split, prompts in splits.items():
            for prompt in prompts:
                reqs.append((split, prompt, rid, dict(sampling_extra)))
                rid += 1
        return reqs

    out = {
        "max_new_tokens": max_new_tokens,
        "mesh_devices": mesh_devices or 1,
        "kv_quantize": "int8",
        "train_wall_s": round(train_s, 1),
        "splits": {k: len(v) for k, v in splits.items()},
    }
    arms = {}
    for mode in modes:
        extra = {"speculative": True} if mode == "on" else {"speculative": True, "gamma": 0}
        engine = make_engine(spec=True)
        t0 = time.perf_counter()
        greedy, per_split = drive(engine, requests(extra))
        wall = time.perf_counter() - t0
        sampled, _ = drive(
            make_engine(spec=True),
            [(s, p, r, dict(x, temperature=0.8, seed=100 + r)) for s, p, r, x in requests(extra)],
        )
        entry = {
            "wall_s": round(wall, 3),
            "pool_bytes": engine.kv_pool_stats()["kv_pool_bytes"],
            "draft_pool_bytes": engine.kv_pool_stats()["draft_kv_pool_bytes"],
        }
        for split, agg in per_split.items():
            entry[f"accepted_per_target_step_{split}"] = (
                round((agg["accepted"] + agg["rounds"]) / agg["rounds"], 4)
                if agg["rounds"] else None
            )
        stats = engine.speculation_stats()
        entry["rounds"] = stats["rounds"]
        entry["fallback_rounds"] = stats["fallback_rounds"]
        arms[mode] = {"entry": entry, "greedy": greedy, "sampled": sampled}
        out[f"spec_{mode}"] = entry
    if "on" in arms and "off" in arms:
        # identity gates: on == off (greedy + fixed-seed sampled, bitwise —
        # same round program both arms) and greedy == the PLAIN paged engine
        plain, _ = drive(
            make_engine(spec=False), [(s, p, r, {}) for s, p, r, x in requests({})]
        )
        out["token_identical_greedy"] = arms["on"]["greedy"] == arms["off"]["greedy"]
        out["token_identical_sampled"] = arms["on"]["sampled"] == arms["off"]["sampled"]
        out["token_identical_vs_plain"] = arms["on"]["greedy"] == plain
        on = out["spec_on"]
        out["aptps_in_distribution"] = on.get("accepted_per_target_step_in_distribution")
        out["aptps_held_out"] = on.get("accepted_per_target_step_held_out")
        out["gates"] = {
            "in_distribution_min": 1.4,
            "held_out_min": 0.95,
            "in_distribution_pass": bool(
                (out["aptps_in_distribution"] or 0) >= 1.4
            ),
            "held_out_pass": bool((out["aptps_held_out"] or 0) >= 0.95),
        }
    return out


def bench_fleet(replica_counts=(1, 2, 4), n_groups=4, n_per_group=8,
                prefix_tokens=24, suffix_tokens=6, max_new_tokens=16, num_slots=2):
    """Fleet scaling phase: a prefix-heavy request mix (``n_groups`` shared
    prefixes × ``n_per_group`` unique suffixes, 1-in-4 interactive) served
    through an :class:`~unionml_tpu.serving.fleet.EngineFleet` at each replica
    count. Replicas split the device set into sub-meshes when it divides
    (:func:`~unionml_tpu.serving.fleet.split_mesh`); otherwise every replica
    shares the default device — routing behavior is still exercised, only the
    throughput scaling flattens.

    Per replica count, two router arms A/B the tentpole claim:

    - ``affinity`` (prefix-digest scoring): group-mates land on the replica
      whose radix cache holds their shared prefix;
    - ``random`` (seeded uniform): the baseline that scatters them.

    The router-level prefix-hit rate is read after a COLD pass (empty digest
    indexes and engine caches — the honest A/B; a warm pass would let random
    routing hit caches that every replica has already filled). Aggregate
    decode tok/s and per-class p99 TTFT come from a second, warm pass so XLA
    compiles stay out of the timings.
    """
    import asyncio
    import contextlib

    import jax

    from unionml_tpu.serving.continuous import DecodeEngine
    from unionml_tpu.serving.fleet import EngineFleet, FleetConfig, split_mesh
    from unionml_tpu.serving.supervisor import EngineSupervisor

    config, model, variables = _bench_gpt()
    rng = np.random.default_rng(0)
    groups = [rng.integers(1, config.vocab_size, size=prefix_tokens).tolist()
              for _ in range(n_groups)]
    requests = []
    for j in range(n_per_group):  # interleave groups: the adversarial arrival order
        for prefix in groups:
            suffix = rng.integers(1, config.vocab_size, size=suffix_tokens).tolist()
            requests.append((prefix + suffix, "interactive" if j % 4 == 0 else "batch"))

    def build(n, policy):
        devices = jax.devices()
        meshes = [None] * n
        if n > 1 and len(devices) % n == 0 and len(devices) // n >= 2:
            parent = _serving_mesh(len(devices), config.num_heads)
            try:
                meshes = split_mesh(parent, n)
            except ValueError:
                meshes = [None] * n
        engines = [
            DecodeEngine(model, variables, num_slots=num_slots, max_len=128,
                         prefill_buckets=(32, 48), mesh=m,
                         prefix_cache_blocks=256, prefix_block_size=8)
            for m in meshes
        ]
        # patient watchdogs: the cold pass holds XLA compiles longer than the
        # default stall timeout, and a degraded-flapping replica would skew
        # the routing A/B
        sups = [EngineSupervisor(stall_timeout_s=120.0) for _ in engines]
        return EngineFleet(
            engines, config=FleetConfig(policy=policy, seed=0), supervisors=sups
        )

    def pct99(xs):
        if not xs:
            return None
        xs = sorted(xs)
        return round(xs[min(int(len(xs) * 0.99), len(xs) - 1)], 2)

    def drive(fleet):
        ttft = {"interactive": [], "batch": []}

        async def one(prompt, cls):
            loop = asyncio.get_running_loop()
            t0, first = loop.time(), True
            agen = fleet.stream(prompt, max_new_tokens, priority=cls)
            async with contextlib.aclosing(agen) as it:
                async for _ in it:
                    if first:
                        ttft[cls].append((loop.time() - t0) * 1e3)
                        first = False

        async def run_all():
            t0 = time.perf_counter()
            await asyncio.gather(*[one(p, cls) for p, cls in requests])
            return time.perf_counter() - t0

        return asyncio.run(run_all()), ttft

    out = {"n_requests": len(requests), "n_groups": n_groups,
           "prefix_tokens": prefix_tokens, "max_new_tokens": max_new_tokens,
           "num_slots": num_slots, "per_replicas": {}}
    for n in replica_counts:
        entry = {}
        for policy in ("affinity", "random"):
            fleet = build(n, policy)
            try:
                drive(fleet)  # cold pass: compiles + the honest hit-rate A/B
                cold = fleet.router.stats()
                total_s, ttft = drive(fleet)  # warm pass: timings
                arm = {
                    "prefix_hit_rate_cold": cold["prefix_hit_rate"],
                    "hit_blocks_cold": cold["hit_blocks"],
                    "lookup_blocks_cold": cold["lookup_blocks"],
                }
                if policy == "affinity":
                    arm["total_s"] = round(total_s, 4)
                    arm["decode_tok_s"] = round(len(requests) * max_new_tokens / total_s, 1)
                    arm["ttft_p99_interactive_ms"] = pct99(ttft["interactive"])
                    arm["ttft_p99_batch_ms"] = pct99(ttft["batch"])
                entry[policy] = arm
            finally:
                fleet.close()
        out["per_replicas"][str(n)] = entry
    return out


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--bert-base", action="store_true", help="bench full BERT-base (TPU)")
    parser.add_argument("--speculative", action="store_true",
                        help="also bench speculative vs plain single-stream generation")
    parser.add_argument("--mesh", type=int, default=0, metavar="N",
                        help="serve the generation benches tensor-parallel over an N-device "
                        "{data, tensor} mesh (params Megatron-split, KV cache head-sharded). "
                        "Runs ONLY the generate + prefill-mix phases, so the hardware-window "
                        "battery can time the sharded path without re-paying the MLP/BERT benches")
    parser.add_argument("--prefill-heavy", action="store_true",
                        help="also bench the prefill-heavy admission mix (batched vs serial "
                        "prefill dispatches)")
    parser.add_argument("--prefix-heavy", action="store_true",
                        help="also bench the prefix-heavy mix (N requests sharing a K-token "
                        "prefix): KV prefix-cache ON vs OFF — prefill tokens recomputed, "
                        "cache hit rate, prefill dispatches")
    parser.add_argument("--slo-mix", action="store_true",
                        help="focused SLO-scheduler phase: mixed interactive (high "
                        "priority, deadline) + batch workload through the asyncio "
                        "batcher, scheduler-on vs FIFO A/B — per-class TTFT/ITL "
                        "p50/p95/p99 plus shed/preempt/deadline-miss counts. Runs "
                        "ONLY this phase (like --pipeline); combine with --mesh N "
                        "to run it over an N-device mesh")
    parser.add_argument("--chaos", action="store_true",
                        help="focused fault-injection smoke: a request flood with an "
                        "injected mid-flood engine failure plus a NaN-logits slot, "
                        "through the supervised batcher — reports recovery latency, "
                        "recovered-token parity vs a clean run, structured-failure "
                        "counts, and pinned-block leaks. Runs ONLY this phase (like "
                        "--slo-mix); combine with --mesh N for the sharded engine")
    parser.add_argument("--fleet", type=int, nargs="+", default=None, metavar="N",
                        help="focused fleet-scaling phase: a prefix-heavy request mix "
                        "through an EngineFleet at each replica count N (devices split "
                        "into per-replica sub-meshes when they divide) — aggregate "
                        "decode tok/s, per-class p99 TTFT, and the router-level "
                        "prefix-affinity vs random-routing cold hit-rate A/B. Runs "
                        "ONLY this phase (like --slo-mix)")
    parser.add_argument("--obs", choices=("on", "off", "ab"), default=None,
                        help="focused telemetry-overhead phase: the same concurrent "
                        "request mix through the asyncio batcher with span tracing + "
                        "metrics ON vs OFF, best-of-3 decode tok/s per arm ('ab' runs "
                        "the pair and GATES: enabled below 0.98x disabled exits "
                        "nonzero — the zero-overhead hook contract, measured). Runs "
                        "ONLY this phase (like --pipeline); combine with --mesh N for "
                        "the sharded engine")
    parser.add_argument("--pipeline", choices=("on", "off", "ab"), default=None,
                        help="focused depth-1 pipelined-decode phase: decode tok/s + "
                        "host-gap ms at lookahead=1 with dispatch-ahead on/off "
                        "('ab' runs the pair and reports the delta). Runs ONLY this "
                        "phase (like --mesh) so the hardware-window battery can time "
                        "the A/B without re-paying the MLP/BERT benches; combine with "
                        "--mesh N to run it over an N-device mesh")
    parser.add_argument("--paged", choices=("on", "off", "ab"), default=None,
                        help="focused paged-vs-dense KV phase: peak concurrent "
                        "requests + decode tok/s at EQUAL KV byte budget (256 "
                        "cached positions as a 4-token block pool vs rigid "
                        "max_len=64 slot rows), plus the slots-vs-memory curve "
                        "('ab' runs the pair and GATES: paged must fit >= 1.5x "
                        "the concurrent requests with token-identical greedy "
                        "streams, else exits nonzero). Runs ONLY this phase "
                        "(like --pipeline); combine with --mesh N for the "
                        "head-sharded pool")
    parser.add_argument("--spec", choices=("on", "off", "ab"), default=None,
                        help="focused adaptive-speculative-decoding phase on the paged "
                        "int8 pool: a trained char-GPT target+draft pair served through "
                        "SpeculativeEngine, in-distribution + adversarial held-out "
                        "prompt splits ('ab' runs spec-on vs the gamma=0 arm at "
                        "identical pool bytes and GATES: accepted-tokens-per-target-"
                        "step >= 1.4 in-distribution AND >= 0.95 held-out, with on-arm "
                        "streams token-identical to the off arm — greedy and "
                        "fixed-seed sampled — and to the plain paged engine, else "
                        "exits nonzero). Runs ONLY this phase (like --paged); combine "
                        "with --mesh N for the head-sharded pools")
    parser.add_argument("--int8", choices=("on", "off", "ab"), default=None,
                        help="focused int8-KV-pool phase: peak concurrent requests "
                        "+ decode tok/s at EQUAL pool byte budget (int8 blocks + "
                        "f32 scales vs the bf16 paged pool), plus the pinned "
                        "quality probe ('ab' runs the pair and GATES: int8 must "
                        "fit >= 1.8x the concurrent requests AND stay within the "
                        "KV_INT8_* logprob-delta/divergence budgets in the same "
                        "run, else exits nonzero). Runs ONLY this phase (like "
                        "--paged); combine with --mesh N for the head-sharded "
                        "pool + scales")
    parser.add_argument(
        "--out",
        default="SERVING_BENCH.json",
        help="artifact path; CPU runs divert to a _cpu-suffixed sibling "
        "(bench_util.resolve_artifact_path) so a local smoke run cannot overwrite "
        "the committed TPU measurements",
    )
    args = parser.parse_args()

    import jax

    from bench_util import resolve_artifact_path

    backend = jax.default_backend()
    if (args.pipeline or args.mesh or args.slo_mix or args.chaos or args.fleet
            or args.obs or args.paged or args.int8 or args.spec):
        import os

        base, ext = os.path.splitext(args.out)
        if args.pipeline:
            base = f"{base}_pipeline"
        if args.paged:
            base = f"{base}_paged"
        if args.int8:
            base = f"{base}_int8"
        if args.spec:
            base = f"{base}_spec"
        if args.obs:
            base = f"{base}_obs"
        if args.slo_mix:
            base = f"{base}_slo"
        if args.chaos:
            base = f"{base}_chaos"
        if args.fleet:
            base = f"{base}_fleet"
        if args.mesh:
            base = f"{base}_mesh{args.mesh}"
        args.out = f"{base}{ext}"
    args.out = resolve_artifact_path(args.out, backend)
    results = {
        "backend": backend,
        "recorded_at": datetime.now(timezone.utc).isoformat(timespec="seconds"),
        "cold_start_excluded": True,
        "models": {},
    }

    if args.fleet:
        fl = bench_fleet(replica_counts=tuple(args.fleet))
        results["models"]["fleet"] = fl
        line = {"metric": "fleet_decode_tok_s", "backend": backend,
                "n_requests": fl["n_requests"]}
        for n, entry in fl["per_replicas"].items():
            line[f"tok_s_r{n}"] = entry["affinity"].get("decode_tok_s")
            line[f"ttft_p99_interactive_r{n}"] = entry["affinity"].get("ttft_p99_interactive_ms")
            line[f"hit_rate_affinity_r{n}"] = entry["affinity"]["prefix_hit_rate_cold"]
            line[f"hit_rate_random_r{n}"] = entry["random"]["prefix_hit_rate_cold"]
        print(json.dumps(line))
        with open(args.out, "w") as fh:
            json.dump(results, fh, indent=2)
        print(f"[bench_serving] wrote {args.out}", file=sys.stderr)
        # the router A/B GATES at >= 2 replicas: affinity losing to random
        # routing means the digest index is broken, fail the battery step
        for n, entry in fl["per_replicas"].items():
            if int(n) >= 2:
                aff = entry["affinity"]["prefix_hit_rate_cold"] or 0.0
                rnd = entry["random"]["prefix_hit_rate_cold"] or 0.0
                if aff <= rnd:
                    return 1
        return 0

    if args.chaos:
        if args.mesh and len(jax.devices()) < args.mesh:
            print(json.dumps({"metric": "chaos_recovery_ms",
                              "error": f"--mesh {args.mesh} needs {args.mesh} devices, "
                              f"found {len(jax.devices())}", "backend": backend}))
            return 1
        chaos = bench_chaos(mesh_devices=args.mesh)
        results["models"]["chaos" + (f"_mesh{args.mesh}" if args.mesh else "")] = chaos
        print(json.dumps({"metric": "chaos_recovery_ms", "backend": backend,
                          "value": chaos["recovery_ms"],
                          "recovered": chaos["recovered"],
                          "failed_structured": chaos["failed_structured"],
                          "parity": chaos["parity"],
                          "pinned_blocks_leaked": chaos["pinned_blocks_leaked"],
                          "mesh_devices": args.mesh or 1}))
        with open(args.out, "w") as fh:
            json.dump(results, fh, indent=2)
        print(f"[bench_serving] wrote {args.out}", file=sys.stderr)
        # the smoke GATES: parity or leaks failing here must fail the battery step
        return 0 if (chaos["parity"] and chaos["pinned_blocks_leaked"] == 0) else 1

    if args.slo_mix:
        if args.mesh and len(jax.devices()) < args.mesh:
            print(json.dumps({"metric": "slo_interactive_p95_ttft_ms",
                              "error": f"--mesh {args.mesh} needs {args.mesh} devices, "
                              f"found {len(jax.devices())}", "backend": backend}))
            return 1
        mix = bench_slo_mix(mesh_devices=args.mesh)
        results["models"]["slo_mix" + (f"_mesh{args.mesh}" if args.mesh else "")] = mix
        line = {"metric": "slo_interactive_p95_ttft_ms", "backend": backend,
                "mesh_devices": args.mesh or 1,
                "scheduler": (mix["scheduler"]["ttft_interactive"] or {}).get("p95_ms"),
                "fifo": (mix["fifo"]["ttft_interactive"] or {}).get("p95_ms"),
                "preemptions": mix["scheduler"]["preemptions"],
                "deadline_misses": mix["scheduler"]["deadline_misses"],
                "sheds": mix["scheduler"]["sheds"]}
        if "interactive_p95_ttft_speedup" in mix:
            line["speedup"] = mix["interactive_p95_ttft_speedup"]
        print(json.dumps(line))
        with open(args.out, "w") as fh:
            json.dump(results, fh, indent=2)
        print(f"[bench_serving] wrote {args.out}", file=sys.stderr)
        return 0

    if args.obs:
        if args.mesh and len(jax.devices()) < args.mesh:
            print(json.dumps({"metric": "obs_decode_tok_s",
                              "error": f"--mesh {args.mesh} needs {args.mesh} devices, "
                              f"found {len(jax.devices())}", "backend": backend}))
            return 1
        modes = ("on", "off") if args.obs == "ab" else (args.obs,)
        ab = bench_obs(modes=modes, mesh_devices=args.mesh)
        results["models"]["obs_ab" if len(modes) == 2 else f"obs_{modes[0]}"] = ab
        line = {"metric": "obs_decode_tok_s", "backend": backend,
                "mesh_devices": args.mesh or 1}
        for mode in modes:
            line[f"tok_s_{mode}"] = ab[f"obs_{mode}"]["decode_tok_s"]
        if len(modes) == 2:
            line["overhead_frac"] = ab["overhead_frac"]
        print(json.dumps(line))
        with open(args.out, "w") as fh:
            json.dump(results, fh, indent=2)
        print(f"[bench_serving] wrote {args.out}", file=sys.stderr)
        # the A/B GATES at 2%: telemetry hooks must stay effectively free on
        # the decode hot path — a bigger regression fails the battery step
        if len(modes) == 2 and ab["overhead_frac"] > 0.02:
            return 1
        return 0

    if args.pipeline:
        if args.mesh and len(jax.devices()) < args.mesh:
            print(json.dumps({"metric": "pipeline_decode_tok_s",
                              "error": f"--mesh {args.mesh} needs {args.mesh} devices, "
                              f"found {len(jax.devices())}", "backend": backend}))
            return 1
        modes = ("on", "off") if args.pipeline == "ab" else (args.pipeline,)
        ab = bench_pipeline(modes=modes, mesh_devices=args.mesh)
        results["models"]["pipeline_ab" if len(modes) == 2 else f"pipeline_{modes[0]}"] = ab
        line = {"metric": "pipeline_decode_tok_s", "backend": backend,
                "mesh_devices": args.mesh or 1}
        for mode in modes:
            line[f"tok_s_{mode}"] = ab[f"pipeline_{mode}"]["decode_tok_s"]
            line[f"fetch_wait_ms_{mode}"] = ab[f"pipeline_{mode}"]["fetch_wait_ms"]
            line[f"host_ms_per_step_{mode}"] = ab[f"pipeline_{mode}"]["host_ms_per_step"]
        if len(modes) == 2:
            line["idle_dispatch_frac_reduction"] = ab["idle_dispatch_frac_reduction"]
            line["speedup_tok_s"] = ab["speedup_tok_s"]
        print(json.dumps(line))
        with open(args.out, "w") as fh:
            json.dump(results, fh, indent=2)
        print(f"[bench_serving] wrote {args.out}", file=sys.stderr)
        return 0

    if args.paged:
        if args.mesh and len(jax.devices()) < args.mesh:
            print(json.dumps({"metric": "paged_peak_concurrent",
                              "error": f"--mesh {args.mesh} needs {args.mesh} devices, "
                              f"found {len(jax.devices())}", "backend": backend}))
            return 1
        modes = ("on", "off") if args.paged == "ab" else (args.paged,)
        ab = bench_paged(modes=modes, mesh_devices=args.mesh)
        results["models"]["paged_ab" if len(modes) == 2 else f"paged_{modes[0]}"] = ab
        line = {"metric": "paged_peak_concurrent", "backend": backend,
                "mesh_devices": args.mesh or 1,
                "kv_token_budget": ab[f"paged_{modes[0]}"]["kv_token_budget"]}
        for mode in modes:
            line[f"peak_concurrent_{mode}"] = ab[f"paged_{mode}"]["peak_concurrent"]
            line[f"tok_s_{mode}"] = ab[f"paged_{mode}"]["decode_tok_s"]
        if len(modes) == 2:
            line["concurrency_ratio"] = ab["concurrency_ratio"]
            line["speedup_tok_s"] = ab["speedup_tok_s"]
            line["token_identical"] = ab["token_identical"]
        print(json.dumps(line))
        with open(args.out, "w") as fh:
            json.dump(results, fh, indent=2)
        print(f"[bench_serving] wrote {args.out}", file=sys.stderr)
        # the A/B GATES the tentpole's claim: at the same KV bytes, paged must
        # pack >= 1.5x the concurrent requests without changing a single token
        if len(modes) == 2 and not (
            ab["concurrency_ratio"] >= 1.5 and ab["token_identical"]
        ):
            return 1
        return 0

    if args.int8:
        if args.mesh and len(jax.devices()) < args.mesh:
            print(json.dumps({"metric": "int8_peak_concurrent",
                              "error": f"--mesh {args.mesh} needs {args.mesh} devices, "
                              f"found {len(jax.devices())}", "backend": backend}))
            return 1
        modes = ("on", "off") if args.int8 == "ab" else (args.int8,)
        ab = bench_int8_kv(modes=modes, mesh_devices=args.mesh)
        results["models"]["int8_ab" if len(modes) == 2 else f"int8_{modes[0]}"] = ab
        line = {"metric": "int8_peak_concurrent", "backend": backend,
                "mesh_devices": args.mesh or 1,
                "pool_byte_budget": ab["pool_byte_budget"]}
        for mode in modes:
            line[f"peak_concurrent_{mode}"] = ab[f"int8_{mode}"]["peak_concurrent"]
            line[f"tok_s_{mode}"] = ab[f"int8_{mode}"]["decode_tok_s"]
            line[f"pool_blocks_{mode}"] = ab[f"int8_{mode}"]["pool_blocks"]
        if len(modes) == 2:
            line["concurrency_ratio"] = ab["concurrency_ratio"]
            line["speedup_tok_s"] = ab["speedup_tok_s"]
            line["divergence_rate"] = ab["quality"]["divergence_rate"]
            line["max_logprob_delta"] = ab["quality"]["max_logprob_delta"]
            line["quality_ok"] = ab["quality"]["quality_ok"]
        print(json.dumps(line))
        with open(args.out, "w") as fh:
            json.dump(results, fh, indent=2)
        print(f"[bench_serving] wrote {args.out}", file=sys.stderr)
        # the A/B GATES the tentpole's claim IN ONE RUN: at the same pool
        # bytes, int8 must pack >= 1.8x the concurrent requests AND hold the
        # pinned logprob-delta/divergence quality budgets
        if len(modes) == 2 and not (
            ab["concurrency_ratio"] >= 1.8 and ab["quality"]["quality_ok"]
        ):
            return 1
        return 0

    if args.spec:
        if args.mesh and len(jax.devices()) < args.mesh:
            print(json.dumps({"metric": "spec_accepted_per_target_step",
                              "error": f"--mesh {args.mesh} needs {args.mesh} devices, "
                              f"found {len(jax.devices())}", "backend": backend}))
            return 1
        modes = ("on", "off") if args.spec == "ab" else (args.spec,)
        ab = bench_spec(modes=modes, mesh_devices=args.mesh)
        results["models"]["spec_ab" if len(modes) == 2 else f"spec_{modes[0]}"] = ab
        line = {"metric": "spec_accepted_per_target_step", "backend": backend,
                "mesh_devices": args.mesh or 1}
        for mode in modes:
            line[f"rounds_{mode}"] = ab[f"spec_{mode}"]["rounds"]
            line[f"wall_s_{mode}"] = ab[f"spec_{mode}"]["wall_s"]
        if len(modes) == 2:
            line["aptps_in_distribution"] = ab["aptps_in_distribution"]
            line["aptps_held_out"] = ab["aptps_held_out"]
            line["token_identical_greedy"] = ab["token_identical_greedy"]
            line["token_identical_sampled"] = ab["token_identical_sampled"]
            line["token_identical_vs_plain"] = ab["token_identical_vs_plain"]
            line["gates_pass"] = bool(
                ab["gates"]["in_distribution_pass"] and ab["gates"]["held_out_pass"]
            )
        print(json.dumps(line))
        with open(args.out, "w") as fh:
            json.dump(results, fh, indent=2)
        print(f"[bench_serving] wrote {args.out}", file=sys.stderr)
        # the A/B GATES the tentpole's claim IN ONE RUN: adaptive gamma must
        # beat vanilla >= 1.4x where the draft helps AND stay >= 0.95x on
        # adversarial traffic, WITHOUT changing a single emitted token
        if len(modes) == 2 and not (
            ab["token_identical_greedy"] and ab["token_identical_sampled"]
            and ab["token_identical_vs_plain"]
            and ab["gates"]["in_distribution_pass"] and ab["gates"]["held_out_pass"]
        ):
            return 1
        return 0

    if args.mesh:
        if len(jax.devices()) < args.mesh:
            print(json.dumps({"metric": "http_generate_p50_ms",
                              "error": f"--mesh {args.mesh} needs {args.mesh} devices, "
                              f"found {len(jax.devices())}", "backend": backend}))
            return 1
        gen = bench_generate(mesh_devices=args.mesh)
        gen_name = ("gpt_tiny" if backend == "cpu" else "gpt2_small") + f"_generate_http_mesh{args.mesh}"
        results["models"][gen_name] = gen
        print(json.dumps({"metric": "http_generate_p50_ms", "value": gen["p50_ms"], "unit": "ms",
                          "model": gen_name, "tokens_per_s_concurrent": gen["tokens_per_s_concurrent"],
                          "mesh_devices": args.mesh, "backend": backend}))
        mix = bench_prefill_mix(mesh_devices=args.mesh)
        results["models"][f"prefill_mix_mesh{args.mesh}"] = mix
        print(json.dumps({"metric": "prefill_admission_speedup", "value": mix["admission_speedup"],
                          "unit": "x", "dispatches": mix["batched"]["prefill_dispatches"],
                          "mesh_devices": args.mesh, "backend": backend}))
        if args.prefix_heavy:
            pfx = bench_prefix_heavy(mesh_devices=args.mesh)
            results["models"][f"prefix_mix_mesh{args.mesh}"] = pfx
            print(json.dumps({"metric": "prefix_prefill_tokens_saved",
                              "value": pfx["prefill_tokens_saved_frac"], "unit": "frac",
                              "hit_rate": pfx["cached"]["hit_rate"],
                              "mesh_devices": args.mesh, "backend": backend}))
        with open(args.out, "w") as fh:
            json.dump(results, fh, indent=2)
        print(f"[bench_serving] wrote {args.out}", file=sys.stderr)
        return 0

    mlp = bench_mlp()
    results["models"]["digits_mlp_64f"] = mlp
    print(json.dumps({"metric": "resident_predict_p50_ms", "value": mlp["p50_ms"], "unit": "ms",
                      "model": "digits_mlp_64f", "p99_ms": mlp["p99_ms"], "backend": backend}))

    bert = bench_bert(base=args.bert_base)
    name = "bert_base_seq128" if args.bert_base else "bert_small_seq128"
    results["models"][name] = bert
    print(json.dumps({"metric": "resident_predict_p50_ms", "value": bert["p50_ms"], "unit": "ms",
                      "model": name, "p99_ms": bert["p99_ms"], "backend": backend}))

    http = bench_http()
    results["models"]["digits_mlp_64f_http"] = http
    print(json.dumps({"metric": "http_predict_p50_ms", "value": http["p50_ms"], "unit": "ms",
                      "model": "digits_mlp_64f_http", "p99_ms": http["p99_ms"], "backend": backend}))

    gen = bench_generate()
    gen_name = "gpt_tiny_generate_http" if backend == "cpu" else "gpt2_small_generate_http"
    results["models"][gen_name] = gen
    print(json.dumps({"metric": "http_generate_p50_ms", "value": gen["p50_ms"], "unit": "ms",
                      "model": gen_name, "tokens_per_s_concurrent": gen["tokens_per_s_concurrent"],
                      "backend": backend}))

    if args.prefill_heavy:
        mix = bench_prefill_mix()
        results["models"]["prefill_mix"] = mix
        print(json.dumps({"metric": "prefill_admission_speedup", "value": mix["admission_speedup"],
                          "unit": "x", "dispatches": mix["batched"]["prefill_dispatches"],
                          "backend": backend}))

    if args.prefix_heavy:
        pfx = bench_prefix_heavy()
        results["models"]["prefix_mix"] = pfx
        print(json.dumps({"metric": "prefix_prefill_tokens_saved",
                          "value": pfx["prefill_tokens_saved_frac"], "unit": "frac",
                          "hit_rate": pfx["cached"]["hit_rate"],
                          "dispatches": pfx["cached"]["prefill_dispatches"],
                          "backend": backend}))

    if args.speculative:
        spec = bench_speculative()
        results["models"]["speculative_vs_plain_http"] = spec
        print(json.dumps({"metric": "speculative_generate_p50_ms",
                          "value": spec["speculative_p50_ms"], "unit": "ms",
                          "plain_p50_ms": spec["plain_p50_ms"],
                          "speedup_p50": spec["speedup_p50"], "backend": backend}))

    with open(args.out, "w") as fh:
        json.dump(results, fh, indent=2)
    print(f"[bench_serving] wrote {args.out}", file=sys.stderr)


if __name__ == "__main__":
    sys.exit(main())

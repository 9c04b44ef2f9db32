"""Does the system start on the chip? One process, the normal entry points.

``python chip_smoke.py`` runs, on one TPU chip, in order:

- ``kernels``: every Pallas entry point compiled by Mosaic (``interpret=False``)
  at one real shape and compared with its XLA reference — flash attention
  forward, forward+backward and packed; the paged kernel as decode (S=1, ragged
  rows, one retired), chunk (S>1) and verify (identity table) over bf16 and int8
  pools, at GPT-2 small's shape (12 heads, 8 slots) and GPT-2 medium's (16
  heads, 48 slots: the benchmark's serving cell).
- ``server_bf16`` / ``server_int8``: GPT-2 small at full width (12 layers, hidden
  768, 12 heads, 1024 positions, vocab 50257, bf16; random weights from a seed)
  behind ``build_aiohttp_app`` on a real socket, engine defaults (paged,
  pipelined, ``paged_attn_impl="auto"``, supervisor and telemetry on) plus a
  prefix cache and a 1024 prefill bucket, eight ``/generate`` requests in flight
  over HTTP with prompts in seven prefill buckets (5 to 600 tokens), then the
  same wave again warm; ``/stats`` and ``/healthz`` checked after it.
- ``trainer``: five ``fit(prefetch=True)`` steps of the BERT-base bf16 B=64
  S=128 classifier, finite loss, native prefetcher in use.

On a host with four chips a ``server_tp4`` phase serves the int8 pool over a
``{"data": 1, "tensor": 4}`` mesh as well. Phase names on the command line run
only those phases.

It needs a TPU: the first thing after ``import jax`` is to require
``jax.default_backend() == "tpu"`` — there is no CPU continuation and no
smaller configuration. Any failed check raises, and the process exits nonzero
without printing a result. The last line of stdout on success is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Times printed here are smoke timings (set-up = first call, compilation
included; warm = the same call again), not benchmark metrics.
"""

import functools
import json
import os
import sys
import threading
import time
import traceback
import types
import urllib.request
from importlib.metadata import version

import jax

if jax.default_backend() != "tpu":
    print(
        f"chip_smoke: needs a TPU, but jax.default_backend() is {jax.default_backend()!r}",
        file=sys.stderr,
    )
    sys.exit(1)

import jax.numpy as jnp
import numpy as np

from unionml_tpu.utils import configure_compile_cache, pick_free_port

#: relative-to-max error allowed between a kernel and its XLA reference when
#: both take bf16 operands (8 mantissa bits: 2**-8 = 0.4% per rounding, a few
#: roundings deep); gradients pass through one more matmul chain
BF16_TOL = 2e-2
BF16_GRAD_TOL = 4e-2

NEW_TOKENS = 48
#: the engine's default prefill buckets plus one over 512, so a prompt past
#: the default ladder is served (a bucket may equal max_len)
PREFILL_BUCKETS = (16, 32, 64, 128, 256, 512, 1024)
#: seven prompts, one in each bucket — no two share one, so admission timing
#: cannot change which (rows, bucket) prefill programs compile. The eighth
#: request shares the longest prompt's first 592 tokens and is admitted as a
#: prefix hit on its sibling whichever of the two arrives first.
PROMPT_LENGTHS = (5, 30, 40, 100, 200, 400, 600)
#: enough pool headroom to keep every prompt of both waves indexed, so the
#: second wave's prefix hits are the same hits on every run
PREFIX_CACHE_BLOCKS = 256
#: the S>1 shapes the kernels phase checks: a small chunk and a long one
CHUNKS = (64, 256)


class CompileCounter:
    """Persistent-cache hits and misses, as JAX reports them."""

    def __init__(self) -> None:
        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def timed(fn, *args):
    """(result, seconds) with the result ready inside the timed region."""
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


def rel_err(got, want) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if got.shape != want.shape or not np.isfinite(got).all():
        raise AssertionError(f"shape {got.shape} vs {want.shape}, finite={np.isfinite(got).all()}")
    return float(np.max(np.abs(got - want)) / (np.max(np.abs(want)) + 1e-6))


def check_kernel(name: str, kernel, reference, args, tol: float) -> None:
    """Compile ``kernel`` (Mosaic) and ``reference`` (XLA), compare, report."""
    kernel, reference = jax.jit(kernel), jax.jit(reference)
    got, setup_s = timed(kernel, *args)
    _, warm_s = timed(kernel, *args)
    want = jax.block_until_ready(reference(*args))
    _, reference_s = timed(reference, *args)
    errs = [rel_err(g, w) for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want))]
    print(
        f"  {name}: set-up {setup_s:.2f}s warm {warm_s * 1e3:.2f}ms "
        f"(XLA reference {reference_s * 1e3:.2f}ms) max-rel-err {max(errs):.2e}"
    )
    if max(errs) > tol:
        raise AssertionError(f"{name}: max relative error {max(errs):.3e} exceeds {tol}")


def phase_kernels() -> None:
    from unionml_tpu.ops.attention import flash_attention, xla_attention
    from unionml_tpu.ops.paged_attention import paged_attention, xla_paged_attention

    rng = np.random.default_rng(0)
    batch, heads, seq, head_dim = 4, 12, 512, 64  # GPT-2 small / BERT-base heads
    q, k, v = (
        jnp.asarray(rng.normal(size=(batch, heads, seq, head_dim)), jnp.bfloat16) for _ in range(3)
    )
    check_kernel(
        "flash forward",
        lambda q, k, v: flash_attention(q, k, v, causal=True),
        lambda q, k, v: xla_attention(q, k, v, causal=True),
        (q, k, v), BF16_TOL,
    )

    def grads(attend):
        def loss(q, k, v):
            return jnp.sum(attend(q, k, v).astype(jnp.float32) ** 2)

        return jax.grad(loss, argnums=(0, 1, 2))

    check_kernel(
        "flash forward+backward",
        grads(lambda q, k, v: flash_attention(q, k, v, causal=True)),
        grads(lambda q, k, v: xla_attention(q, k, v, causal=True)),
        (q, k, v), BF16_GRAD_TOL,
    )
    segments = np.zeros((batch, seq), np.int32)  # three documents and a padding tail
    segments[:, :100], segments[:, 100:300], segments[:, 300:460] = 1, 2, 3
    segments = jnp.asarray(segments)
    check_kernel(
        "flash packed",
        lambda q, k, v: flash_attention(q, k, v, segment_ids=segments, causal=True),
        lambda q, k, v: xla_attention(q, k, v, segment_ids=segments, causal=True),
        (q, k, v), BF16_TOL,
    )

    # the paged kernel at the shapes the server phase traces (GPT-2 small: 12
    # heads, 8 slots) and at the benchmark's serving cell (GPT-2 medium: 16
    # heads, 48 slots): 16-token blocks, table width 65 (max_len 1024 + scratch)
    block_size, width = 16, 65

    def pool_leaves(n_blocks, heads, quantized, code_dtype):
        """((k, v), scales) of a random pool. bf16: the one joined leaf (rows of
        ``[key | value]``), no ``v`` and no scales."""
        shape = (n_blocks, heads, block_size, head_dim)
        if not quantized:
            joined = rng.normal(size=shape[:3] + (2 * head_dim,))
            return [jnp.asarray(joined, jnp.bfloat16), None], []
        codes = [jnp.asarray(rng.integers(-127, 128, shape), code_dtype) for _ in range(2)]
        scales = [
            jnp.asarray(rng.uniform(0.005, 0.02, (n_blocks, heads, 1, 1)), jnp.float32)
            for _ in range(2)
        ]
        return codes, scales

    def positional(attend):
        def run(q, k, v, table, base, *scales):
            k_scale, v_scale = scales or (None, None)
            return attend(q, k, v, table, base, k_scale=k_scale, v_scale=v_scale,
                          out_dtype=jnp.bfloat16)

        return run

    kernel = positional(functools.partial(paged_attention, impl="pallas"))
    reference = positional(xla_paged_attention)

    for model, heads, slots in (("gpt2-small", 12, 8), ("gpt2-medium", 16, 48)):
        blocks = slots * (width - 1) + 1

        def queries(rows, seq):
            return jnp.asarray(rng.normal(size=(rows, heads, seq, head_dim)), jnp.bfloat16)

        for pool in ("bf16", "int8"):
            (k, v), scales = pool_leaves(blocks, heads, pool == "int8", jnp.int8)
            # every slot owns a shuffled run of blocks; the last column is scratch
            table = rng.permutation(blocks - 1).reshape(slots, width - 1).astype(np.int32)
            table = jnp.asarray(np.concatenate([table, np.full((slots, 1), blocks - 1, np.int32)], 1))
            # ragged rows, the first a retired one on the sentinel base
            base = rng.integers(0, (width - 1) * block_size - 64, slots).astype(np.int32)
            base[0] = (width - 1) * block_size
            base = jnp.asarray(base)
            check_kernel(f"paged decode S=1 ({model}, {pool})", kernel, reference,
                         (queries(slots, 1), k, v, table, base, *scales), BF16_TOL)
            for chunk in CHUNKS:
                check_kernel(f"paged chunk S={chunk} ({model}, {pool})", kernel, reference,
                             (queries(1, chunk), k, v, table[1:2], base[1:2], *scales), BF16_TOL)
            # speculative verify: the row's gathered blocks as a local pool behind an
            # identity table, int8 codes carried as exact integers in f32
            (k, v), scales = pool_leaves(slots * width, heads, pool == "int8", jnp.float32)
            identity = jnp.arange(slots * width, dtype=jnp.int32).reshape(slots, width)
            check_kernel(f"paged verify ({model}, {pool})", kernel, reference,
                         (queries(slots, 1), k, v, identity, base, *scales), BF16_TOL)

    # the latent shape of the benchmark's sparse-decoder cell: 32 query heads
    # over ONE key head whose rows (640 wide: 512 + 64 + padding) are the values
    # too, 32 slots of 8192 positions in the cell's 128-token blocks (three a
    # step of the copied walk in decode), a 1024-token chunk
    block_size = 128
    heads, row, slots, width = 32, 640, 32, 8192 // block_size + 1
    blocks = slots * (width - 1) + 1
    pool = jnp.asarray(rng.normal(size=(blocks, 1, block_size, row)), jnp.bfloat16)
    table = rng.permutation(blocks - 1).reshape(slots, width - 1).astype(np.int32)
    table = jnp.asarray(np.concatenate([table, np.full((slots, 1), blocks - 1, np.int32)], 1))
    base = rng.integers(256, 7000, slots).astype(np.int32)
    base[0] = (width - 1) * block_size
    base = jnp.asarray(base)

    def latent(impl):
        def run(q, pool, table, base):
            return paged_attention(q, pool, None, table, base, impl=impl, sm_scale=0.1)

        return run

    for name, rows, seq in (("decode S=1", slots, 1), ("chunk S=1024", 1, 1024)):
        q = jnp.asarray(rng.normal(size=(rows, heads, seq, row)), jnp.bfloat16)
        check_kernel(f"paged {name} (latent: 32 heads on 1 key row of 640, keys as values)",
                     latent("pallas"), latent("xla"), (q, pool, table[:rows], base[-rows:]), BF16_TOL)


class Server:
    """The aiohttp app on a real socket, served from a background thread."""

    def __init__(self, app) -> None:
        import asyncio

        from aiohttp import web

        self.port = pick_free_port()
        self._loop = asyncio.new_event_loop()
        self._error = None
        started = threading.Event()

        def serve():
            asyncio.set_event_loop(self._loop)
            runner = web.AppRunner(app)

            async def boot():
                await runner.setup()  # runs the app's startup hook: the engine is built here
                await web.TCPSite(runner, "127.0.0.1", self.port).start()

            try:
                self._loop.run_until_complete(boot())
            except BaseException as exc:  # handed to the constructor's caller below
                self._error = exc
                started.set()
                return
            started.set()
            self._loop.run_forever()
            self._loop.run_until_complete(runner.cleanup())

        self._thread = threading.Thread(target=serve, daemon=True)
        self._thread.start()
        if not started.wait(600):
            raise RuntimeError("server did not start within 600 s")
        if self._error is not None:
            raise RuntimeError("server failed to start") from self._error

    def get(self, path: str):
        with urllib.request.urlopen(f"http://127.0.0.1:{self.port}{path}", timeout=60) as resp:
            return resp.status, json.loads(resp.read())

    def generate(self, prompt, new_tokens: int):
        request = urllib.request.Request(
            f"http://127.0.0.1:{self.port}/generate",
            data=json.dumps({"prompt_ids": prompt, "max_new_tokens": new_tokens}).encode(),
            headers={"Content-Type": "application/json"},
        )
        # the first wave waits on compilation, so the client is patient
        with urllib.request.urlopen(request, timeout=900) as resp:
            return resp.status, json.loads(resp.read())

    def stop(self) -> None:
        self._loop.call_soon_threadsafe(self._loop.stop)
        self._thread.join(timeout=60)
        if self._thread.is_alive():
            raise RuntimeError("server thread did not stop within 60 s")


def wave(server: Server, prompts) -> float:
    """All prompts in flight at once; every answer must be a 200 with exactly
    the asked number of tokens. Returns the wave's wall time."""
    results = [None] * len(prompts)

    def one(i):
        try:
            results[i] = server.generate(prompts[i], NEW_TOKENS)
        except Exception as exc:  # re-raised on the main thread below
            results[i] = exc

    threads = [threading.Thread(target=one, args=(i,), daemon=True) for i in range(len(prompts))]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=1000)
    elapsed = time.perf_counter() - t0
    for prompt, result in zip(prompts, results):
        if result is None:
            raise RuntimeError(f"/generate (prompt of {len(prompt)}) did not answer in 1000 s")
        if isinstance(result, Exception):
            raise RuntimeError(f"/generate (prompt of {len(prompt)}) failed") from result
        status, body = result
        if status != 200 or len(body["tokens"]) != NEW_TOKENS:
            raise AssertionError(
                f"/generate (prompt of {len(prompt)}): status {status}, "
                f"{len(body.get('tokens', []))} tokens, wanted 200 and {NEW_TOKENS}"
            )
    return elapsed


def phase_server(kv_quantize=None, tensor_parallel: int = 1) -> None:
    from unionml_tpu.models.gpt import GPTConfig, GPTLMHeadModel, init_params
    from unionml_tpu.ops.paged_attention import resolve_paged_impl
    from unionml_tpu.serving import build_aiohttp_app
    from unionml_tpu.serving.continuous import DecodeEngine

    t0 = time.perf_counter()
    config = GPTConfig(dropout=0.0)  # GPT-2 small, bf16
    model = GPTLMHeadModel(config)
    variables = init_params(config, rng=jax.random.PRNGKey(0))
    mesh = None
    if tensor_parallel > 1:
        from jax.experimental import mesh_utils

        from unionml_tpu.parallel import make_mesh

        chips = jax.devices()[:tensor_parallel]
        mesh = make_mesh({"data": 1, "tensor": tensor_parallel}, devices=chips)
        # make_mesh falls back to a plain reshape when the topology-aware
        # assignment refuses the shape; on a real host it must not have
        ici_order = mesh_utils.create_device_mesh((1, tensor_parallel), devices=chips)
        if not (mesh.devices == ici_order).all():
            raise AssertionError("make_mesh did not keep the topology-aware device order")

    def engine():
        return DecodeEngine(
            model, variables, num_slots=8, max_len=1024, prefill_buckets=PREFILL_BUCKETS,
            kv_quantize=kv_quantize, mesh=mesh,
        )

    app = build_aiohttp_app(
        types.SimpleNamespace(name="gpt2-small-smoke", artifact=object()),
        resident=False, coalesce=False, generator=engine,
        generate_prefix_cache_blocks=PREFIX_CACHE_BLOCKS,
    )
    server = Server(app)
    try:
        rng = np.random.default_rng(1)

        def prompts():
            wave = [rng.integers(0, config.vocab_size, n).tolist() for n in PROMPT_LENGTHS]
            sibling = wave[-1][:592] + rng.integers(0, config.vocab_size, 8).tolist()
            return wave + [sibling]

        first = prompts()
        wave(server, first)  # misses: every prefill bucket, the hit-suffix and decode programs
        wave(server, first)  # all prefix hits
        _, warmed = server.get("/stats")
        setup_s = time.perf_counter() - t0

        if mesh is not None:
            engine_state = app["continuous_batcher"].engine
            for name in ("_lens", "_last_logits", "_active_dev", "_remaining_dev", "_tables",
                         "_temp_dev", "_top_k_dev", "_top_p_dev"):
                spread = len(getattr(engine_state, name).sharding.device_set)
                print(f"  engine.{name}: on {spread} device(s)")

        # the same again, warm: half repeats (prefix hits), half fresh prompts
        second = [a if i % 2 else b for i, (a, b) in enumerate(zip(first, prompts()))]
        warm_s = wave(server, second)

        _, stats = server.get("/stats")
        generation = stats["generation"]
        pool = generation["prefix_cache"]
        expected_impl = resolve_paged_impl("auto", 65, 16, config.num_heads, config.head_dim)
        expected_dtype = kv_quantize or "bfloat16"
        if pool["impl"] != expected_impl or pool["kv_dtype"] != expected_dtype:
            raise AssertionError(
                f"/stats says impl={pool['impl']} kv_dtype={pool['kv_dtype']}, "
                f"wanted {expected_impl} and {expected_dtype}"
            )
        if not pool["hits"] or pool["evicted_blocks"]:
            raise AssertionError(f"prefix cache: {pool['hits']} hits, {pool['evicted_blocks']} evictions")
        robustness = generation["robustness"]
        before = warmed["generation"]["robustness"]
        faults = {
            key: robustness[key]
            for key in ("engine_failures", "engine_rebuilds", "quarantined_requests",
                        "failures", "rebuilds", "failed_requests")
        }
        faults["watchdog_trips_after_warm_up"] = (
            robustness["watchdog_trips"] - before["watchdog_trips"]
        )
        if any(faults.values()):
            raise AssertionError(f"robustness counters are not zero: {faults}")
        status, health = server.get("/healthz")
        if status != 200 or health["state"] != "ok":
            raise AssertionError(f"/healthz: {status} {health}")
        tokens = len(second) * NEW_TOKENS
        print(
            f"  impl={pool['impl']} kv_dtype={pool['kv_dtype']} "
            f"kv_pool_bytes={pool['kv_pool_bytes']} prefix hits {pool['hits']}/{pool['lookups']}\n"
            f"  set-up {setup_s:.1f}s (watchdog trips while compiling: "
            f"{before['watchdog_trips']}); warm wave {warm_s:.2f}s for "
            f"{len(second)} requests, {tokens} tokens"
        )
    finally:
        server.stop()


def phase_trainer() -> None:
    from unionml_tpu.models import BertConfig, BertForSequenceClassification, create_train_state
    from unionml_tpu.models.bert import init_params
    from unionml_tpu.models.training import fit
    from unionml_tpu.native import PrefetchLoader

    batch, seq, steps = 64, 128, 5
    config = BertConfig.base(dtype=jnp.bfloat16)
    model = BertForSequenceClassification(config)
    state = create_train_state(
        model, init_params(config, rng=jax.random.PRNGKey(0), seq_len=seq),
        learning_rate=2e-5, warmup_steps=10, total_steps=1000,
    )
    rng = np.random.default_rng(2)
    rows = batch * steps
    data = {
        "input_ids": rng.integers(0, config.vocab_size, (rows, seq)).astype(np.int32),
        "attention_mask": np.ones((rows, seq), np.int32),
        "labels": rng.integers(0, config.num_labels, (rows,)).astype(np.int32),
    }
    probe = PrefetchLoader(data, batch)  # what fit(prefetch=True) builds
    native = probe.uses_native
    probe.close()
    if not native:
        raise AssertionError("fit(prefetch=True) would batch in Python: the native library did not build")
    t0 = time.perf_counter()
    result = fit(
        state, data, batch_size=batch, num_steps=steps, log_every=1, prefetch=True,
        input_signature=("input_ids", "attention_mask"),
    )
    total_s = time.perf_counter() - t0
    losses = [entry["loss"] for entry in result.metrics_history]
    if result.steps != steps or len(losses) != steps - 1 or not np.isfinite(losses).all():
        raise AssertionError(f"fit: steps={result.steps}, losses={losses}")
    print(
        f"  {result.steps} steps, native prefetch, losses {[round(x, 4) for x in losses]}\n"
        f"  set-up {total_s - result.wall_time_s:.1f}s (first step, compile included); "
        f"warm {result.wall_time_s:.2f}s for {steps - 1} steps"
    )


PHASES = {
    "kernels": phase_kernels,
    "server_bf16": phase_server,
    "server_int8": lambda: phase_server(kv_quantize="int8"),
    "trainer": phase_trainer,
    "server_tp4": lambda: phase_server(kv_quantize="int8", tensor_parallel=4),
}


def main(argv) -> None:
    unknown = [name for name in argv if name not in PHASES]
    if unknown:
        raise SystemExit(f"unknown phase(s) {unknown}; known: {list(PHASES)}")
    devices = jax.devices()
    selected = argv or [
        name for name in PHASES if name != "server_tp4" or len(devices) >= 4
    ]
    cache_dir = configure_compile_cache()
    counter = CompileCounter()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    print(
        f"platform={device['platform']} device_kind={device['kind']!r} devices={device['count']} "
        f"jax={jax.__version__} jaxlib={version('jaxlib')} libtpu={version('libtpu')}\n"
        f"compile cache: {cache_dir}"
    )
    for name in selected:
        print(f"[{name}]")
        hits, misses, t0 = counter.hits, counter.misses, time.perf_counter()
        PHASES[name]()
        print(
            f"  phase wall {time.perf_counter() - t0:.1f}s; compiled {counter.misses - misses} "
            f"program(s), loaded {counter.hits - hits} from the cache"
        )
    print(f"compiled {counter.misses} program(s) in all, loaded {counter.hits} from the cache")
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    # Exit hard either way once the verdict is out: a server or prefetch thread
    # that failed to stop must not hold the process — and the chip — open.
    try:
        main(sys.argv[1:])
    except BaseException:  # graftlint: disable=swallowed-exception -- the traceback is printed and the process exits 1 on the next lines
        traceback.print_exc()
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(1)
    sys.stdout.flush()
    os._exit(0)

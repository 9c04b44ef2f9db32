"""The serving driver: the program's aiohttp app on a loopback socket in this
process, the load generator in a process of its own, one window, one check.

What is timed is ``POST /generate`` with ``stream=true`` on
``build_aiohttp_app(generator=DecodeEngine(...))`` — the entry a user starts
with ``unionml-tpu serve``. The driver takes from the program the app, its
``/stats`` counters and its request traces; weights, traffic, clocks, the trace
reduction and the reference are the benchmark's. What depends on the model's
architecture is asked of the configuration's family (``cell.family()``) and of
its reference (``cell.reference()``).
"""

from __future__ import annotations

import asyncio
import gc
import json
import os
import subprocess
import sys
import threading
import time
import types
import urllib.request
from pathlib import Path
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import common, traffic
from perfbench.loadgen import GRACE_S

HERE = Path(__file__).resolve().parent


class Server:
    """The aiohttp app on a real socket, served from a background thread."""

    def __init__(self, app) -> None:
        from aiohttp import web

        from unionml_tpu.utils import pick_free_port

        self.port = pick_free_port()
        self._loop = asyncio.new_event_loop()
        self._error: Optional[BaseException] = None
        started = threading.Event()

        def serve() -> None:
            asyncio.set_event_loop(self._loop)
            runner = web.AppRunner(app)

            async def boot() -> None:
                await runner.setup()  # the app's startup hook builds the engine here
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
        if not started.wait(900):
            raise RuntimeError("server did not start within 900 s")
        if self._error is not None:
            raise RuntimeError("server failed to start") from self._error

    def post(self, path: str, body: Dict[str, Any], timeout: float = 1100) -> Any:
        request = urllib.request.Request(
            f"http://127.0.0.1:{self.port}{path}", data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(request, timeout=timeout) as resp:
            return json.loads(resp.read().split(b"\n")[-2] if body.get("stream") else resp.read())

    def stop(self) -> None:
        self._loop.call_soon_threadsafe(self._loop.stop)
        self._thread.join(timeout=120)
        if self._thread.is_alive():
            raise RuntimeError("server thread did not stop within 120 s")


def build_app(config: Dict[str, Any], params: Any, family: Any):
    from unionml_tpu.serving import build_aiohttp_app
    from unionml_tpu.serving.continuous import DecodeEngine
    from unionml_tpu.serving.telemetry import Telemetry

    deployment = config["perfbench"]
    model = family.model(config)
    variables = {"params": params}

    def engine():
        return DecodeEngine(model, variables, **deployment["engine"])

    app_options = dict(deployment.get("app", {}))
    journal = app_options.pop("telemetry_journal_size", 256)
    return build_aiohttp_app(
        types.SimpleNamespace(name=config["perfbench"]["name"], artifact=object()),
        resident=False, coalesce=False, generator=engine,
        generate_telemetry=Telemetry(journal_size=journal), **app_options,
    )


def reachable_buckets(config: Dict[str, Any], mix: Dict[str, Any]) -> List[tuple]:
    """``(bucket, a prompt length that lands in it)`` for every prefill bucket
    the mix's prompt lengths can reach."""
    from unionml_tpu.serving.continuous import DEFAULT_PREFILL_BUCKETS

    engine = config["perfbench"]["engine"]
    buckets = sorted(engine.get("prefill_buckets", DEFAULT_PREFILL_BUCKETS))
    lengths = sorted(set(int(p) for p, _ in traffic.size_pool(mix)))
    out, low = [], 0
    for bucket in buckets:
        inside = [n for n in lengths if low < n <= bucket]
        if inside:
            out.append((bucket, inside[-1]))
        low = bucket
    return out


def warm_up(server: Server, config: Dict[str, Any], mix: Dict[str, Any], seed: int,
            counter: common.CompileCounter) -> Dict[str, Any]:
    """Run every ``(rows, bucket)`` prefill program the mix can reach, and the
    decode step, before the window. A batch of ``rows`` prompts in one request is
    queued as one admission; a wave that looked up no new program (admission
    split it otherwise) is sent again."""
    rng = np.random.default_rng([int(seed), 0x3A93])
    vocab = config["vocab_size"]
    rows_max = int(config["perfbench"]["engine"].get("prefill_batch", 4))
    waves = retries = 0
    server.post("/generate", {"prompt_ids": rng.integers(0, vocab, 8).tolist(),
                              "max_new_tokens": 2, "stream": True})
    for bucket, length in reachable_buckets(config, mix):
        for rows in range(1, rows_max + 1):
            for attempt in range(4):
                before = counter.lookups
                prompts = [rng.integers(0, vocab, length).tolist() for _ in range(rows)]
                body = {"prompts": prompts, "max_new_tokens": 1}
                if rows == 1:
                    body = {"prompt_ids": prompts[0], "max_new_tokens": 1}
                server.post("/generate", body)
                waves += 1
                if counter.lookups > before:
                    break
                retries += 1
    return {"waves": waves, "retries": retries}


def start_loadgen(server: Server, cell, args) -> subprocess.Popen:
    command = [
        sys.executable, str(HERE / "loadgen.py"), "--port", str(server.port),
        "--traffic", str(cell.mix_path), "--seed", str(args.seed),
        "--vocab", str(cell.config["vocab_size"]), "--seconds", str(args.seconds),
    ]
    env = {k: v for k, v in os.environ.items() if k != "BENCH_RUN"}
    env["JAX_PLATFORMS"] = "cpu"  # it imports no JAX; if that ever changed, not the chip
    return subprocess.Popen(command, stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
                            env=env)


def drive(server: Server, cell, args, counter: common.CompileCounter,
          tracer: Optional[common.Tracer], phases: common.Phases) -> Dict[str, Any]:
    """Run the load generator through one window; returns its result with the
    compile-cache misses and the traced interval on the host's clock."""
    proc = start_loadgen(server, cell, args)
    result: Optional[Dict[str, Any]] = None
    misses_open = misses_close = None
    try:
        for line in proc.stdout:
            line = line.strip()
            if not line.startswith("{"):
                continue
            event = json.loads(line)
            if event["event"] == "ramped":
                if tracer is not None:
                    tracer.start()
                proc.stdin.write("go\n")
                proc.stdin.flush()
            elif event["event"] == "open":
                phases.mark("window_open")
                misses_open = counter.misses
                if tracer is not None:
                    tracer.stop_after(cell.mix.get("trace_seconds", 6.0))
            elif event["event"] == "close":
                phases.mark("window_closed")
                misses_close = counter.misses
            elif event["event"] == "result":
                result = event
        proc.wait(timeout=120)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)
        if tracer is not None:
            tracer.finish()
            phases.mark("trace_read")
    if result is None or proc.returncode != 0:
        raise RuntimeError(f"the load generator ended with code {proc.returncode} and no result")
    result["compiles_in_window"] = (misses_close or 0) - (misses_open or 0)
    return result


# ----------------------------------------------------------------- reduction


def window_tokens(result: Dict[str, Any], lo: Optional[float] = None,
                  hi: Optional[float] = None) -> List[tuple]:
    """``(arrival, live_length)`` of every token that arrived in ``[lo, hi)``:
    the keys its decode step attended over, the new token included."""
    lo = result["t_open"] if lo is None else lo
    hi = result["t_close"] if hi is None else hi
    out = []
    for record in result["records"]:
        for j, at in enumerate(record["token_times"]):
            if lo <= at < hi:
                out.append((at, record["prompt_len"] + j + 1))
    return out


def end_to_end(result: Dict[str, Any], mix: Dict[str, Any], grace: float = GRACE_S) -> Dict[str, Any]:
    """The serving cells' end-to-end numbers, from the client's stamps alone."""
    t_open, t_close = result["t_open"], result["t_close"]
    length = t_close - t_open
    out: Dict[str, Any] = {}
    # every gap between consecutive tokens whose later token fell in the window, with when it ended
    stamped = [
        ((b - a) * 1e3, b - t_open)
        for record in result["records"]
        for a, b in zip(record["token_times"], record["token_times"][1:]) if t_open <= b < t_close
    ]
    gaps = [gap for gap, _ in stamped]
    out["serve_tokens_per_s"] = len(window_tokens(result)) / length
    if gaps:
        out["itl_p95_ms"] = traffic.percentile(gaps, 95)
        out["itl_tail_mean_ms"] = traffic.tail_mean(gaps, 0.10)
        out["itl_samples"] = len(gaps)
        for q in (50, 90, 99):  # beside the metrics, for the reader of a run by hand
            out[f"itl_p{q}_ms_seen"] = traffic.percentile(gaps, q)
        # a stall of the whole server shows here, with when in the window it ended
        out["itl_max_ms_seen"], out["itl_max_at_s_seen"] = max(stamped)
    trips = [
        (result.get(key) or {}).get("generation", {}).get("robustness", {}).get("watchdog_trips")
        for key in ("stats_open", "stats_close")
    ]
    if None not in trips:
        out["watchdog_trips_seen"] = trips[1] - trips[0]
    if mix["arrival"]["mode"] == "open":
        due = [r for r in result["records"] if t_open <= r["due"] < t_close]
        ttft = [
            ((r["token_times"][0] if r["token_times"] else t_close + grace) - r["due"]) * 1e3
            for r in due
        ]
        if ttft:
            out["ttft_p90_ms"] = traffic.percentile(ttft, 90)
        out["late_ms"] = [(r["sent"] - r["due"]) * 1e3 for r in due if r["sent"] is not None]
        out["attempted"] = len(due)
        out["failed"] = sum(1 for r in due if not r["token_times"])
    else:
        counted = [
            r for r in result["records"]
            if r["sent"] is not None and r["sent"] < t_close
            and (r["token_times"][-1:] or [t_close])[0] >= t_open
        ]
        out["attempted"] = len(counted)
        out["failed"] = sum(
            1 for r in counted
            if r["error"] != "dropped_at_close" and (r["status"] != 200 or r["error"] is not None)
        )
    return out


# --------------------------------------------------------------------- check


def sample_finished(result: Dict[str, Any], seed: int, want: int) -> List[Dict[str, Any]]:
    """Requests that finished inside the window: the longest answer, then a
    draw from the seed, ``want`` in all."""
    t_open, t_close = result["t_open"], result["t_close"]
    finished = [
        r for r in result["records"]
        if r["done"] and r["tokens"] and t_open <= r["token_times"][-1] < t_close
    ]
    if not finished:
        return []
    finished.sort(key=lambda r: (-len(r["tokens"]), r["index"]))
    chosen = [finished[0]]
    rest = finished[1:]
    order = np.random.default_rng([int(seed), 0x5A3B]).permutation(len(rest))
    chosen.extend(rest[i] for i in order[: max(0, want - 1)])
    return chosen


def check(result: Dict[str, Any], cell, params: Any, seed: int, control: bool) -> Dict[str, Any]:
    """Compare served tokens with the float32 reference: over a sample of the
    requests the window finished, the widest gap by which a served token's
    reference logit lies below the reference's best.

    With ``control`` it also reads, at the same positions, the gap of the token
    that the reference in each lower precision puts first: the readings the
    limit has to fail.
    """
    config, limits = cell.config, cell.limits
    stream = traffic.RequestStream(cell.mix, seed, config["vocab_size"])
    sample = sample_finished(result, seed, int(limits["sample_requests"]))
    pad_to = int(limits["reference_pad_to"])
    max_rows = int(cell.mix["output_tokens"].get("max", cell.mix["output_tokens"].get("value", 0)))
    reference = cell.reference()
    kw = cell.family().reference_kwargs(config)
    worst = 0.0
    controls = tuple(limits["controls"]) if control else ()
    worst_control = {mode: 0.0 for mode in controls}
    tokens_compared = short = 0
    for record in result["records"]:
        if record["done"] and len(record["tokens"]) != record["asked"]:
            short += 1
    for record in sample:
        prompt = stream.prompt(record["index"])
        served = record["tokens"]
        ids = np.zeros((1, pad_to), np.int32)
        seq = prompt + served[:-1]
        ids[0, : len(seq)] = seq
        # as many rows as the mix's longest answer in every call: one program to compile
        rows = np.minimum(np.arange(len(prompt) - 1, len(prompt) - 1 + max_rows), pad_to - 1)
        # reduced on the host: eager device operations would compile per answer length
        picks = np.arange(len(served))
        logits = np.asarray(
            reference.logits_at(params, jnp.asarray(ids), jnp.asarray(rows), **kw)
        )[: len(served)]
        best = logits.max(axis=-1)
        worst = max(worst, float((best - logits[picks, served]).max()))
        tokens_compared += len(served)
        for mode in controls:
            low = np.asarray(
                reference.logits_at(params, jnp.asarray(ids), jnp.asarray(rows), lowp=mode, **kw)
            )[: len(served)]
            gap = best - logits[picks, low.argmax(axis=-1)]
            worst_control[mode] = max(worst_control[mode], float(gap.max()))
    numbers = {
        "logit_gap": [worst, limits["logit_gap"]],
        "short_answers": [short, 0],
        "requests_compared": [len(sample), None],
        "tokens_compared": [tokens_compared, None],
    }
    numbers.update({f"control_{mode}_logit_gap": [gap, None] for mode, gap in worst_control.items()})
    correct = bool(sample) and worst <= limits["logit_gap"] and short == 0
    return {"correct": correct, "numbers": numbers}


# ----------------------------------------------------------------------- run


def run(cell, args, t0: float) -> Dict[str, Any]:
    from unionml_tpu.utils import configure_compile_cache

    configure_compile_cache()
    counter = common.CompileCounter()
    phases = common.Phases(t0, counter)
    phases.mark("imported")
    config = cell.config
    deployment = config["perfbench"]
    family = cell.family()
    params = family.make_params(config, args.seed, deployment["weights_dtype"])
    jax.block_until_ready(params)
    phases.mark("weights")
    app = build_app(config, params, family)
    server = Server(app)
    phases.mark("server_ready")
    tracer = common.Tracer() if args.trace else None
    try:
        warm = warm_up(server, config, cell.mix, args.seed, counter)
        phases.mark("warmed_up")
        result = drive(server, cell, args, counter, tracer, phases)
    finally:
        server.stop()
    phases.mark("server_stopped")
    memory_peak = common.memory_peak_bytes()
    setup_s = result["t_open"] - t0
    e2e = end_to_end(result, cell.mix)
    e2e["setup_s"] = setup_s

    # free the program's state before the reference runs
    del app, server
    gc.collect()

    checked = check(result, cell, params, args.seed, control=bool(args.control))
    phases.mark("checked")
    context = {
        "cell": cell, "config": config, "family": family, "mix": cell.mix, "load": result, "e2e": e2e,
        "trace": tracer.summary if tracer is not None else None,
        "trace_interval": tracer.interval if tracer is not None else None,
        "compiles_in_window": result["compiles_in_window"], "warm_up": warm,
        "phases": phases.marks,
        "window_tokens": window_tokens,
    }
    return {
        "context": context, "e2e": e2e, "checked": checked, "memory_peak_bytes": memory_peak,
        "attempted": e2e.pop("attempted"), "failed": e2e.pop("failed"),
    }

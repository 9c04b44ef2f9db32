"""Native HTTP serving app (aiohttp): ``/``, ``/predict``, ``/health``.

Reference parity: ``unionml/fastapi.py:15-70`` — same endpoints, same request contract
(``inputs`` = reader kwargs, or ``features`` = raw features), same startup model-load
from ``UNIONML_MODEL_PATH`` or from backend lineage. Built on aiohttp rather than
FastAPI so the framework serves without optional deps; a FastAPI adapter with the same
handlers lives in :mod:`unionml_tpu.serving.fastapi_adapter`.

The prediction path goes through :class:`~unionml_tpu.serving.resident.ResidentPredictor`
— the resident XLA executable, not interpreted re-dispatch.
"""

import os
from http import HTTPStatus
from typing import Any, Optional

import numpy as np

from unionml_tpu._logging import logger
from unionml_tpu.serving.resident import ResidentPredictor

_INDEX_HTML = """
<html>
  <head><title>unionml-tpu</title></head>
  <body>
    <h1>unionml-tpu</h1>
    <p>TPU-native model training and serving</p>
  </body>
</html>
"""


def jsonable(value: Any) -> Any:
    """Convert predictions (device arrays, numpy, pandas) to JSON-serializable values."""
    import jax

    if isinstance(value, jax.Array):
        value = np.asarray(jax.device_get(value))
    if isinstance(value, np.ndarray):
        return value.tolist()
    if isinstance(value, (np.generic,)):
        return value.item()
    if hasattr(value, "to_dict") and not isinstance(value, dict):
        try:
            return value.to_dict(orient="records")
        except TypeError:
            return value.to_dict()
    if isinstance(value, (list, tuple)):
        return [jsonable(v) for v in value]
    if isinstance(value, dict):
        return {k: jsonable(v) for k, v in value.items()}
    return value


def load_model_artifact(
    model: Any,
    remote: bool = False,
    app_version: Optional[str] = None,
    model_version: str = "latest",
    model_path: Optional[str] = None,
) -> None:
    """Startup model resolution (``fastapi.py:22-34`` parity)."""
    if model.artifact is not None:
        return
    model_path = model_path or os.getenv("UNIONML_MODEL_PATH")
    if not remote:
        if model_path is None:
            raise ValueError(
                "Model artifact path not specified: pass --model-path to `unionml-tpu serve` (local mode)."
            )
        model.load(model_path)
    else:
        from unionml_tpu.remote import get_model_artifact

        model.artifact = get_model_artifact(model, app_version=app_version, model_version=model_version)


def build_aiohttp_app(
    model: Any,
    remote: bool = False,
    app_version: Optional[str] = None,
    model_version: str = "latest",
    resident: bool = True,
    coalesce: bool = True,
    max_batch: int = 64,
    max_wait_ms: float = 2.0,
    buckets: Optional[Any] = None,
    seq_buckets: Optional[Any] = None,
    example_features: Optional[Any] = None,
    generator: Optional[Any] = None,
    generate_lookahead: int = 1,
    generate_prefix_cache_blocks: int = 0,
    generate_prefix_block_size: int = 16,
    generate_scheduler: Optional[Any] = None,
    generate_supervisor: Optional[Any] = None,
    generate_drain_s: float = 5.0,
    generate_replicas: int = 1,
    generate_fleet_config: Optional[Any] = None,
    generate_telemetry: Any = True,
    generate_trace_journal: Optional[str] = None,
    retry_jitter_rng: Optional[Any] = None,
    mesh: Optional[Any] = None,
    param_specs: Optional[Any] = None,
):
    """Create the aiohttp application with a resident predictor.

    ``coalesce=True`` merges concurrent row-list ``features`` requests into shared
    predictor calls (see :mod:`unionml_tpu.serving.batcher`); requests whose payloads
    don't fit the row-list contract fall back to per-request prediction.

    ``mesh`` serves the resident predictor across a device mesh (see
    :class:`ResidentPredictor`): parameters commit to the mesh at startup
    (``param_specs`` lays them out, else replicated) and request batches shard
    over the ``data`` axis. The endpoint contract (``/predict``, ``/health``,
    ``/stats``) is unchanged above the sharded executor; for a mesh-sharded
    ``/generate`` pass a ``generator`` built with ``DecodeEngine(..., mesh=...)``.
    Under a mesh, coalesced flushes prefer multiples of the mesh's batch shards
    so merged batches shard evenly instead of padding up.

    ``seq_buckets`` enables sequence-length bucketing for tokenized inputs, and
    ``example_features`` (a request-shaped row list) drives startup warmup for
    multi-input models — see :class:`ResidentPredictor`.

    ``generator`` enables the continuous-batching ``POST /generate`` route for
    decoder models: a :class:`~unionml_tpu.serving.continuous.DecodeEngine`, a
    :class:`~unionml_tpu.serving.continuous.ContinuousBatcher`, or a zero-arg
    callable returning either — the callable form is evaluated at startup, AFTER
    the model artifact loads, so the engine can be built from trained variables.
    ``generate_lookahead`` sets the decode steps fused per device dispatch when
    the app wraps a bare engine (see :meth:`DecodeEngine.step`).

    ``generate_prefix_cache_blocks`` > 0 enables KV **prefix caching** on the
    served engine at startup (``generate_prefix_block_size`` tokens per block,
    see :meth:`DecodeEngine.enable_prefix_cache`) unless the engine already has
    one: requests sharing a prompt prefix (system prompts, chat history)
    restore its KV from a device block pool and prefill only their suffix.
    Cache hit/eviction counters surface under ``GET /stats`` →
    ``generation.prefix_cache``.

    ``generate_scheduler`` configures the SLO admission scheduler when the app
    wraps a bare engine (a
    :class:`~unionml_tpu.serving.scheduler.SchedulerConfig` or a prebuilt
    :class:`~unionml_tpu.serving.scheduler.SLOScheduler`; ``None`` = default
    policy). ``/generate`` payloads may carry ``priority``
    (``interactive``/``standard``/``batch``) and ``deadline_ms``; overload
    sheds map to HTTP 429/503 with ``Retry-After``, deadline expiry to 504,
    invalid requests to 400 — every error response shares ONE machine-readable
    envelope, ``{"error": {"code", "reason", "detail", "retry_after_ms"?}}``
    (``retry_after_ms`` is jittered so shed clients never retry in lockstep) —
    and scheduler counters surface under ``GET /stats`` →
    ``generation.scheduler``.

    ``generate_supervisor`` configures engine supervision when the app wraps a
    bare engine: ``None`` (default) builds an
    :class:`~unionml_tpu.serving.supervisor.EngineSupervisor` — engine
    failures salvage and RESUME every recoverable request token-identically,
    NaN-logits quarantine per request, a watchdog flags fetch stalls, and
    ``GET /healthz`` serves the health state machine (200 while
    ``ok``/``degraded``, 503 while ``rebuilding``/``failed``, with the last
    fault's reason). Pass a prebuilt supervisor to tune its knobs, or
    ``False`` to disable supervision. Shutdown drains gracefully: new
    submissions fail fast while in-flight work finishes for up to
    ``generate_drain_s`` seconds before the batcher closes. Robustness
    counters (faults injected/observed, rebuilds, recovered vs failed
    requests, quarantines, watchdog trips) surface under ``GET /stats`` →
    ``generation.robustness``.

    ``generate_replicas`` > 1 serves a FLEET
    (:class:`~unionml_tpu.serving.fleet.EngineFleet`): ``generator`` must
    then be a callable returning a bare ``DecodeEngine`` — it is invoked once
    per replica (receiving ``replica=i`` when its signature accepts it, so a
    factory can place each engine on its own sub-mesh; see
    :func:`~unionml_tpu.serving.fleet.split_mesh`) — or a prebuilt
    ``EngineFleet``. Requests route by prefix affinity, session stickiness
    (``/generate`` payloads may carry a ``session_id`` string), and
    load/health (``generate_fleet_config``, a
    :class:`~unionml_tpu.serving.fleet.FleetConfig`, tunes the router);
    ``/healthz`` and ``/stats`` → ``generation.fleet`` report per-replica
    state. Fleet replicas are always supervised (failover depends on it), so
    ``generate_supervisor=False`` is rejected, and ``generate_scheduler``
    must be a config, not a prebuilt scheduler instance.

    ``generate_telemetry`` wires the serving telemetry subsystem
    (:class:`~unionml_tpu.serving.telemetry.Telemetry`) into the generator at
    startup: per-request span traces (``GET /trace/{request_id}``,
    ``GET /traces/recent``), Prometheus metrics (``GET /metrics``), and a
    ``telemetry`` block under ``GET /stats`` that solo and fleet deployments
    share. ``True`` (default) builds one; pass a prebuilt ``Telemetry`` to
    share instruments with a harness, or ``False``/``None`` to disable — the
    request path then pays one host ``is not None`` branch per hook site and
    nothing else. ``generate_trace_journal`` names a JSONL file completed
    traces append to (schema v1; the replay-simulator input). Every
    ``/generate`` request is assigned a ``request_id`` (echoed in the
    response, in error envelopes, and in request-path log lines) that keys
    its trace.

    ``retry_jitter_rng`` (a ``random.Random``) seeds the ±25% Retry-After
    jitter on shed responses — by default a module-global RNG (production:
    de-correlated retries); a seeded instance makes shed envelopes
    reproducible for tests and A/B harnesses.
    """
    from aiohttp import web

    from unionml_tpu.serving.resident import DEFAULT_BUCKETS

    app = web.Application()
    predictor = (
        ResidentPredictor(
            model,
            buckets=buckets or DEFAULT_BUCKETS,
            seq_buckets=seq_buckets,
            example_features=example_features,
            mesh=mesh,
            param_specs=param_specs,
        )
        if resident
        else None
    )
    batcher = None
    if coalesce and predictor is not None:
        from unionml_tpu.serving.batcher import RequestBatcher

        preferred_multiple = None
        if mesh is not None:
            from unionml_tpu.parallel.mesh import batch_axis_size

            n_shards = batch_axis_size(mesh)
            preferred_multiple = n_shards if n_shards > 1 else None
        batcher = RequestBatcher(
            lambda rows: predictor.predict(features=rows),
            max_batch=max_batch,
            max_wait_ms=max_wait_ms,
            preferred_multiple=preferred_multiple,
        )

    async def on_startup(app):
        load_model_artifact(model, remote=remote, app_version=app_version, model_version=model_version)
        if predictor is not None:
            # graftlint: disable=async-blocking -- startup hook: the warmup compile+sync runs before the server accepts any traffic, so blocking the (idle) loop here is the point
            predictor.setup()
        if generator is not None:
            import inspect

            from unionml_tpu.serving.continuous import ContinuousBatcher, DecodeEngine
            from unionml_tpu.serving.fleet import EngineFleet
            from unionml_tpu.serving.scheduler import SLOScheduler
            from unionml_tpu.serving.supervisor import EngineSupervisor
            from unionml_tpu.serving.telemetry import Telemetry

            telemetry = None
            if generate_telemetry:
                telemetry = (
                    generate_telemetry
                    if isinstance(generate_telemetry, Telemetry)
                    else Telemetry(journal_path=generate_trace_journal)
                )

            def _enable_cache(target):
                if (
                    generate_prefix_cache_blocks
                    and isinstance(target, DecodeEngine)
                    and target.prefix_cache is None
                ):
                    target.enable_prefix_cache(
                        generate_prefix_cache_blocks, generate_prefix_block_size
                    )

            prebuilt = isinstance(generator, (DecodeEngine, ContinuousBatcher, EngineFleet))
            if generate_replicas > 1 and not prebuilt:
                # fleet mode: the factory builds one bare engine per replica
                # (each on its own sub-mesh when the factory takes `replica`)
                if generate_supervisor is not None:
                    # False would disable the failover layer the fleet is
                    # built on; a single prebuilt supervisor can't be shared
                    # across replicas (pass supervisors= to EngineFleet)
                    raise ValueError(
                        "generate_replicas > 1 builds one supervisor per "
                        "replica; generate_supervisor must be left None"
                    )
                if isinstance(generate_scheduler, SLOScheduler):
                    raise ValueError(
                        "generate_replicas > 1 needs a SchedulerConfig (each "
                        "replica owns its own scheduler), not an SLOScheduler"
                    )
                takes_replica = "replica" in inspect.signature(generator).parameters
                engines = []
                for i in range(int(generate_replicas)):
                    engine = generator(replica=i) if takes_replica else generator()
                    if not isinstance(engine, DecodeEngine):
                        raise TypeError(
                            f"fleet generator must return a DecodeEngine per "
                            f"replica, got {type(engine)!r}"
                        )
                    _enable_cache(engine)
                    engines.append(engine)
                built = EngineFleet(
                    engines,
                    config=generate_fleet_config,
                    lookahead=generate_lookahead,
                    scheduler=generate_scheduler,
                    telemetry=telemetry,
                )
            else:
                built = generator() if callable(generator) and not prebuilt else generator
                if isinstance(built, EngineFleet):
                    for rep in built.replicas:
                        _enable_cache(rep.engine)
                else:
                    _enable_cache(built.engine if isinstance(built, ContinuousBatcher) else built)
                if isinstance(built, DecodeEngine):
                    # supervision is ON by default for app-owned batchers: engine
                    # failures recover instead of failing the house (False opts out)
                    supervisor = generate_supervisor
                    if supervisor is None:
                        supervisor = EngineSupervisor()
                    elif supervisor is False:
                        supervisor = None
                    built = ContinuousBatcher(
                        built, lookahead=generate_lookahead, scheduler=generate_scheduler,
                        supervisor=supervisor, telemetry=telemetry,
                    )
            if telemetry is not None:
                # prebuilt batchers/fleets get the same wiring post-hoc (no-op
                # when the caller already attached one — theirs wins)
                attach = getattr(built, "attach_telemetry", None)
                if callable(attach):
                    attach(telemetry)
            app["telemetry"] = getattr(built, "_telemetry", None) or telemetry
            app["continuous_batcher"] = built
        logger.info("Serving app ready (model=%s).", model.name)

    async def on_cleanup(app):
        if batcher is not None:
            batcher.close()
        gen = app.get("continuous_batcher")
        if gen is not None:
            # graceful drain: stop admitting, let in-flight work finish (or
            # time out into prompt structured failures), then close
            drain = getattr(gen, "drain", None)
            if callable(drain):
                # graftlint: disable=async-blocking -- shutdown hook: the server already stopped accepting; blocking the (dying) loop for the bounded drain is the point
                drain(generate_drain_s)
            else:
                # graftlint: disable=async-blocking -- shutdown hook, same contract as drain above
                gen.close()

    app.on_startup.append(on_startup)
    app.on_cleanup.append(on_cleanup)

    async def index(request):
        return web.Response(text=_INDEX_HTML, content_type="text/html")

    async def health(request):
        if model.artifact is None:
            return web.json_response({"detail": "Model artifact not found."}, status=500)
        return web.json_response({"message": HTTPStatus.OK.phrase, "status": HTTPStatus.OK.value})

    async def healthz(request):
        """Load-balancer health: the supervisor's state machine, 503 while the
        engine cannot serve (``rebuilding``/``failed``) so a router drains
        this replica instead of timing out against it. Apps without a
        supervised generator report on the model artifact alone."""
        gen = request.app.get("continuous_batcher")
        if gen is not None and getattr(gen, "is_fleet", False):
            # fleet shape: per-replica supervisor states; the fleet serves
            # (200) while ANY replica does — "degraded" flags reduced capacity
            body = gen.healthz()
            return web.json_response(
                body, status=200 if body["state"] in ("ok", "degraded") else 503
            )
        sup = getattr(gen, "supervisor", None) if gen is not None else None
        if sup is None:
            state = "ok" if model.artifact is not None else "failed"
            body = {"state": state, "supervised": False, "last_fault": None}
        else:
            stats = sup.stats()
            body = {
                "state": stats["health"],
                "supervised": True,
                "last_fault": sup.last_fault,
                "watchdog_trips": stats["watchdog_trips"],
                "rebuilds": stats["rebuilds"],
            }
        serving = body["state"] in ("ok", "degraded")
        return web.json_response(body, status=200 if serving else 503)

    async def predict(request):
        try:
            payload = await request.json()
        except Exception as exc:
            return web.json_response({"detail": f"Request body must be JSON: {exc}"}, status=422)
        inputs = payload.get("inputs")
        features = payload.get("features")
        if inputs is None and features is None:
            return web.json_response({"detail": "inputs or features must be supplied."}, status=500)
        import asyncio

        loop = asyncio.get_running_loop()
        try:
            # empty {} means reader-defaults ONLY when no features came along —
            # a boilerplate empty inputs key must not shadow a real features payload
            if inputs is not None and (inputs or features is None):
                # off the event loop: compiled predictor calls block for milliseconds+
                result = await loop.run_in_executor(
                    None,
                    lambda: predictor.predict(**inputs) if predictor is not None else model.predict(**inputs),
                )
            else:
                result = None
                if batcher is not None and isinstance(features, list):
                    try:
                        result = await batcher.submit(features)
                    except Exception as exc:
                        logger.info("Coalesced path failed (%s); serving this request directly.", exc)
                if result is None:
                    # model.predict runs the feature pipeline itself; don't pre-process here
                    result = await loop.run_in_executor(
                        None,
                        lambda: predictor.predict(features=features)
                        if predictor is not None
                        else model.predict(features=features),
                    )
            # jsonable() may device_get prediction arrays (graftlint
            # async-blocking true positive, fixed): fetch off the event loop,
            # like the predictor calls above
            payload = await loop.run_in_executor(None, jsonable, result)
            return web.json_response(payload)
        except Exception as exc:
            logger.exception("Prediction failed")
            return web.json_response({"detail": f"Prediction failed: {exc}"}, status=500)

    def _error_response(status, reason, detail, retry_after_s=None, request_id=None):
        """The ONE machine-readable error envelope every non-200 on this app
        uses — 400/429/500/503/504 all share it, so clients parse one shape:

            {"error": {"code": int, "reason": slug, "detail": str,
                       "retry_after_ms": int?, "request_id": str?}}

        ``request_id`` (present on every ``/generate`` failure) keys the
        request's span trace — ``GET /trace/{request_id}`` answers "what
        happened to THIS request" for sheds and failures alike.

        ``retry_after_ms`` (and the ``Retry-After`` header) carry ±25% JITTER:
        a shed wave handed one exact retry delay would come back as a
        synchronized thundering herd — the spread de-correlates the retries.
        The jitter draws from ``retry_jitter_rng`` when the app was built
        with one (seeded tests assert exact envelopes); default stays the
        module-global RNG.
        """
        import random

        error = {"code": int(status), "reason": reason, "detail": detail}
        if request_id is not None:
            error["request_id"] = request_id
        headers = {}
        if retry_after_s:
            draw = retry_jitter_rng.random if retry_jitter_rng is not None else random.random
            jittered = float(retry_after_s) * (0.75 + 0.5 * draw())
            error["retry_after_ms"] = int(jittered * 1000)
            headers["Retry-After"] = str(max(1, round(jittered)))
        return web.json_response({"error": error}, status=status, headers=headers)

    def _bad_request(detail, reason="invalid_request", request_id=None):
        """Client-side rejection: machine-readable ``reason`` + human detail."""
        return _error_response(400, reason, detail, request_id=request_id)

    def _scheduling_response(exc, request_id=None):
        """Map a structured scheduling rejection to its HTTP contract:
        queue-full sheds are 429, infeasible-deadline sheds are 503 (both with
        jittered ``Retry-After``), and deadline expiry is 504 — each carrying
        the error's machine-readable ``reason`` so clients can branch without
        parsing prose."""
        from unionml_tpu.serving.scheduler import (
            DeadlineExceededError,
            DeadlineInfeasibleError,
            QueueFullError,
        )

        if isinstance(exc, QueueFullError):
            status = 429
        elif isinstance(exc, DeadlineInfeasibleError):
            status = 503
        elif isinstance(exc, DeadlineExceededError):
            status = 504
        else:
            status = 500
        return _error_response(
            status, getattr(exc, "reason", "scheduling"), str(exc),
            retry_after_s=getattr(exc, "retry_after_s", None),
            request_id=request_id,
        )

    def _engine_failure_response(exc, request_id=None):
        """An engine-side structured failure: 503 when a retry can plausibly
        succeed (rebuilding, transient fault — another replica, or this one in
        a moment), 500 when it cannot — either way the reason slug travels,
        never a generic stringified 500."""
        retryable = bool(getattr(exc, "retryable", False))
        return _error_response(
            503 if retryable else 500, getattr(exc, "reason", "engine_failure"), str(exc),
            retry_after_s=1.0 if retryable else None,
            request_id=request_id,
        )

    async def generate_route(request):
        from unionml_tpu.serving.faults import EngineFailure
        from unionml_tpu.serving.scheduler import SchedulingError, parse_priority
        from unionml_tpu.serving.telemetry import new_request_id

        # minted at route entry so EVERY outcome — 400s included — carries an
        # id the client can quote; for a single-prompt request the same id
        # keys the span trace (GET /trace/{request_id})
        request_id = new_request_id()
        gen = request.app.get("continuous_batcher")
        if gen is None:
            return _error_response(
                404, "not_enabled", "Generation is not enabled on this app.",
                request_id=request_id,
            )
        try:
            payload = await request.json()
        except Exception as exc:
            return _bad_request(
                f"Request body must be JSON: {exc}", reason="invalid_json",
                request_id=request_id,
            )
        prompt_ids = payload.get("prompt_ids")
        prompts = payload.get("prompts")
        if prompt_ids is None and prompts is None:
            return _bad_request(
                "prompt_ids (one prompt) or prompts (a batch) must be supplied.",
                request_id=request_id,
            )
        import asyncio

        try:
            max_new = int(payload.get("max_new_tokens", 32))
        except (TypeError, ValueError):
            return _bad_request("max_new_tokens must be an integer.", request_id=request_id)
        if max_new < 1:
            # pre-validated here so the streaming path can reject BEFORE
            # committing a 200 status line (the engine's check would be too late)
            return _bad_request("max_new_tokens must be >= 1.", request_id=request_id)

        try:
            # validate EVERY prompt before scheduling any: a bad prompt in a
            # batch must not leave its siblings burning decode slots for a
            # response that will never be delivered (TypeError covers
            # non-numeric tokens / a non-list prompts value)
            for p in [prompt_ids] if prompt_ids is not None else prompts:
                seq = np.asarray(p, dtype=np.int32).reshape(-1)
                if seq.size == 0:
                    raise ValueError("empty prompt")
                if seq.size >= gen.engine.max_len:
                    raise ValueError(f"prompt length {seq.size} >= max_len ({gen.engine.max_len})")
                gen.engine.check_prefillable(int(seq.size))
        except (TypeError, ValueError) as exc:
            return _bad_request(f"invalid prompt payload: {exc}", request_id=request_id)

        # optional SLO fields: a priority class and a wall-clock deadline
        # budget (ms, arrival -> completion), forwarded to the generator's
        # scheduler only when present so custom generators without the
        # scheduler kwargs keep working
        slo = {}
        if payload.get("priority") is not None:
            try:
                slo["priority"] = parse_priority(payload["priority"])
            except ValueError as exc:
                return _bad_request(str(exc), request_id=request_id)
        if payload.get("deadline_ms") is not None:
            deadline_ms = payload["deadline_ms"]
            if (
                isinstance(deadline_ms, bool)
                or not isinstance(deadline_ms, (int, float))
                or deadline_ms <= 0
            ):
                return _bad_request(
                    f"deadline_ms must be a positive number, got {deadline_ms!r}",
                    request_id=request_id,
                )
            slo["deadline_ms"] = float(deadline_ms)
        if payload.get("session_id") is not None:
            session_id = payload["session_id"]
            if not isinstance(session_id, str) or not session_id:
                return _bad_request(
                    f"session_id must be a non-empty string, got {session_id!r}",
                    request_id=request_id,
                )
            # session stickiness is a fleet-router concept; forwarded only to
            # a fleet generator (a single batcher has no session kwarg, and a
            # sessionless deployment should not reject the field)
            if getattr(gen, "is_fleet", False):
                slo["session_id"] = session_id

        # optional per-request sampling controls (applied to every prompt in a
        # batch); absent keys defer to the engine's construction-time settings
        from unionml_tpu.ops.sampling import validate_sampling

        try:
            temp, top_k, top_p = validate_sampling(
                payload.get("temperature"),
                payload.get("top_k") if payload.get("top_k") is not None else 0,
                payload.get("top_p") if payload.get("top_p") is not None else 1.0,
            )
        except (TypeError, ValueError) as exc:
            return _bad_request(f"invalid sampling params: {exc}", request_id=request_id)
        sampling = {}
        if payload.get("temperature") is not None:
            sampling["temperature"] = temp
        if payload.get("top_k") is not None:
            sampling["top_k"] = top_k
        if payload.get("top_p") is not None:
            sampling["top_p"] = top_p
        stream = bool(payload.get("stream"))
        if stream and prompt_ids is None:
            return _bad_request(
                "stream=true requires a single prompt_ids prompt.", request_id=request_id
            )
        # forward the route's id into the generator's trace when it can carry
        # it (single prompt only: each prompt of a batch opens its OWN trace,
        # while the route-level id still identifies the HTTP request)
        rid_kw = (
            {"request_id": request_id}
            if getattr(gen, "accepts_request_id", False)
            else {}
        )
        if stream:
            import contextlib
            import json as _json

            # pull the FIRST token before committing the 200 status line, so
            # scheduling rejections (queue full / infeasible or expired
            # deadline) surface as their real 429/503/504 statuses instead of
            # an in-band error on a 200 stream
            stream_it = gen.stream(prompt_ids, max_new, **slo, **sampling, **rid_kw)
            exhausted, first = False, None
            try:
                first = await anext(stream_it)
            except StopAsyncIteration:
                exhausted = True  # zero emitted tokens (e.g. immediate eos)
            except SchedulingError as exc:
                await stream_it.aclose()
                return _scheduling_response(exc, request_id=request_id)
            except EngineFailure as exc:
                await stream_it.aclose()
                return _engine_failure_response(exc, request_id=request_id)
            except ValueError as exc:
                await stream_it.aclose()
                return _bad_request(str(exc), request_id=request_id)
            except Exception as exc:
                await stream_it.aclose()
                logger.exception("Generation failed (request_id=%s)", request_id)
                return _error_response(
                    500, "internal", f"Generation failed: {exc}", request_id=request_id
                )

            # ndjson chunks: one {"token": N} line per decoded token, then a
            # {"done": true, "tokens": [...]} trailer. Failures from here on
            # can only be reported in-band as an {"error": ...} line (the
            # status line is already out)
            response = web.StreamResponse()
            response.content_type = "application/x-ndjson"
            await response.prepare(request)
            tokens = []
            try:
                # aclosing guarantees the stream iterator closes promptly on an
                # early exit (client disconnect -> write raises), which cancels
                # the request's decode slot
                async with contextlib.aclosing(stream_it) as it:
                    if not exhausted:
                        tokens.append(first)
                        await response.write((_json.dumps({"token": first}) + "\n").encode())
                        async for token in it:
                            tokens.append(token)
                            await response.write((_json.dumps({"token": token}) + "\n").encode())
                await response.write(
                    (_json.dumps({"done": True, "tokens": tokens, "request_id": request_id})
                     + "\n").encode()
                )
            except Exception as exc:
                logger.warning(
                    "Streaming generation ended early (request_id=%s): %s", request_id, exc
                )
                line = {"error": str(exc), "request_id": request_id}
                reason = getattr(exc, "reason", None)
                if reason is not None:
                    # a deadline expiring (or the engine failing) mid-stream
                    # lands here: the status is committed, so the reason slug
                    # travels in-band instead
                    line["reason"] = reason
                try:  # the transport may be the thing that failed
                    await response.write((_json.dumps(line) + "\n").encode())
                except Exception:  # graftlint: disable=swallowed-exception -- writing the in-band error line to a transport that may itself be the failure: nothing is left to tell
                    pass
            try:
                await response.write_eof()
            except Exception:  # graftlint: disable=swallowed-exception -- eof on a possibly-dead transport: the request is already finished either way
                pass
            return response
        try:
            if prompt_ids is not None:
                tokens = await gen.generate(prompt_ids, max_new, **slo, **sampling, **rid_kw)
                return web.json_response({"tokens": tokens, "request_id": request_id})
            completions = await asyncio.gather(
                *(gen.generate(p, max_new, **slo, **sampling) for p in prompts)
            )
            return web.json_response(
                {"completions": list(completions), "request_id": request_id}
            )
        except SchedulingError as exc:  # structured shed / deadline rejection
            return _scheduling_response(exc, request_id=request_id)
        except EngineFailure as exc:  # engine-side structured failure (recovery taxonomy)
            return _engine_failure_response(exc, request_id=request_id)
        except ValueError as exc:  # bad request (empty/oversized prompt, bad budget)
            return _bad_request(str(exc), request_id=request_id)
        except Exception as exc:  # engine/worker failures are SERVER errors
            logger.exception("Generation failed (request_id=%s)", request_id)
            return _error_response(
                500, "internal", f"Generation failed: {exc}", request_id=request_id
            )

    async def stats(request):
        payload = {"model": model.name, "resident": predictor is not None}
        if predictor is not None and hasattr(predictor, "device_stats"):
            # server-side device latency (dispatch + fetch), split from HTTP RTT
            payload["device_latency"] = predictor.device_stats()
        gen = request.app.get("continuous_batcher")
        if gen is not None and getattr(gen, "is_fleet", False):
            # fleet shape: aggregate counters + generation.fleet with the
            # router block and per-replica scheduler/supervisor/cache state
            payload["generation"] = gen.stats()
        elif gen is not None:
            # every generator kind (continuous engine, speculative facade)
            # surfaces the same counter set; getattr defaults keep the route
            # total even for a custom generator exposing only the core triple
            payload["generation"] = {
                "num_slots": gen.engine.num_slots,
                "active": gen.engine.num_active,
                "max_len": gen.engine.max_len,
                "requests_admitted": getattr(gen.engine, "requests_admitted", 0),
                "tokens_decoded": getattr(gen.engine, "tokens_decoded", 0),
            }
            spec_stats = getattr(gen.engine, "speculation_stats", None)
            if callable(spec_stats):
                # speculative decoding observability: acceptance EMA, current
                # adaptive γ, round/fallback counters, and the accepted-tokens-
                # per-target-step ratio
                payload["generation"]["speculation"] = spec_stats()
            pipeline_stats = getattr(gen.engine, "pipeline_stats", None)
            if callable(pipeline_stats):
                # pipelined-decode observability: depth, dispatch and idle
                # counters, the active-slot integral and the loop thread's
                # phase counters (seconds, entries, duration buckets)
                payload["generation"]["pipeline"] = pipeline_stats()
            if getattr(gen.engine, "prefix_cache", None) is not None:
                # hit rate + eviction churn for the KV prefix cache, plus the
                # engine's FLOP counter the hits shrink
                payload["generation"]["prefix_cache"] = gen.engine.prefix_cache.stats()
                payload["generation"]["prefill_tokens_computed"] = (
                    gen.engine.prefill_tokens_computed
                )
                kv_stats = getattr(gen.engine, "kv_pool_stats", None)
                if callable(kv_stats):
                    # pool dtype + resident bytes (stored vs priced at the
                    # dense compute dtype) — the kv_quantize="int8" saving
                    payload["generation"]["prefix_cache"].update(kv_stats())
            sched = getattr(gen, "scheduler", None)
            if sched is not None and callable(getattr(sched, "stats", None)):
                # SLO scheduler observability: per-class queue depth,
                # queue-wait EMA, shed / preemption / deadline-miss counters —
                # the same block whichever generator kind is plugged in
                payload["generation"]["scheduler"] = sched.stats()
            # robustness observability: engine-side failure/quarantine/fault
            # counters merged with the supervisor's health + recovery counters
            robustness = {}
            engine_stats = getattr(gen.engine, "robustness_stats", None)
            if callable(engine_stats):
                robustness.update(engine_stats())
            sup = getattr(gen, "supervisor", None)
            if sup is not None and callable(getattr(sup, "stats", None)):
                robustness.update(sup.stats())
            if robustness:
                payload["generation"]["robustness"] = robustness
        tel = request.app.get("telemetry")
        if tel is not None:
            # the ONE schema solo and fleet share: trace/journal state plus a
            # snapshot of every registry instrument (the same counters the
            # Prometheus /metrics endpoint renders), so a client reads one
            # block whichever deployment shape is behind the route
            payload["telemetry"] = {**tel.stats(), "metrics": tel.metrics.snapshot()}
            if "generation" in payload and getattr(tel, "slo", None) is not None:
                # per-class SLO attainment + multi-window burn rate, identical
                # solo/fleet (the tracker sits on the shared Telemetry, above
                # whichever generator shape feeds it)
                payload["generation"]["slo"] = tel.slo.report()
        if batcher is not None:
            payload["coalescing"] = dict(batcher.stats)
            if batcher.ema_gap_ms is not None:
                payload["coalescing"]["ema_gap_ms"] = round(batcher.ema_gap_ms, 3)
        return web.json_response(payload)

    async def metrics_route(request):
        """``GET /metrics``: Prometheus text exposition (format 0.0.4) of the
        serving registry — one scrape target whichever generator shape
        (solo engine, fleet) is behind the app."""
        tel = request.app.get("telemetry")
        if tel is None:
            return _error_response(404, "not_enabled", "Telemetry is not enabled on this app.")
        return web.Response(
            body=tel.metrics.render().encode("utf-8"),
            headers={"Content-Type": "text/plain; version=0.0.4; charset=utf-8"},
        )

    async def trace_route(request):
        """``GET /trace/{request_id}``: the request's full span tree (active
        or recently completed) — admission, queue wait, routing, prefix
        restore, prefill chunks, decode, preemption/quarantine/failover, and
        the terminal status."""
        tel = request.app.get("telemetry")
        if tel is None:
            return _error_response(404, "not_enabled", "Telemetry is not enabled on this app.")
        rid = request.match_info["request_id"]
        trace = tel.get_trace(rid)
        if trace is None:
            return _error_response(
                404, "trace_not_found",
                f"no active or recent trace for request_id {rid!r} "
                f"(the journal ring may have evicted it)",
                request_id=rid,
            )
        return web.json_response(trace)

    async def traces_recent(request):
        """``GET /traces/recent?n=K``: the journal ring's most recent completed
        traces, newest first (JSONL schema v1 objects)."""
        tel = request.app.get("telemetry")
        if tel is None:
            return _error_response(404, "not_enabled", "Telemetry is not enabled on this app.")
        try:
            n = int(request.query.get("n", 50))
        except (TypeError, ValueError):
            return _bad_request("n must be an integer.")
        return web.json_response({"traces": tel.recent(n)})

    app.router.add_get("/", index)
    app.router.add_get("/health", health)
    app.router.add_get("/healthz", healthz)
    app.router.add_get("/stats", stats)
    app.router.add_get("/metrics", metrics_route)
    app.router.add_get("/trace/{request_id}", trace_route)
    app.router.add_get("/traces/recent", traces_recent)
    app.router.add_post("/predict", predict)
    app.router.add_post("/generate", generate_route)
    app["unionml_model"] = model
    app["resident_predictor"] = predictor
    app["request_batcher"] = batcher
    app["telemetry"] = None  # set at startup when a generator is wired
    return app


def run_app(app, host: str = "127.0.0.1", port: int = 8000) -> None:
    from aiohttp import web

    from unionml_tpu.utils import configure_compile_cache

    configure_compile_cache()  # the startup hook compiles the predictor / engine
    web.run_app(app, host=host, port=port)

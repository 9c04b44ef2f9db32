"""Continuous batching for the GPT generation serving path.

The reference serves predictions one request at a time through FastAPI
(``unionml/fastapi.py:50-64``); its hot loop is a single predictor call. For
autoregressive generation that design wastes the accelerator: a new request must
wait for every in-flight generation to finish. Continuous batching — the
vLLM/Orca serving discipline — keeps ONE compiled decode step running over a
fixed set of slots, inserting incoming requests into free slots *between steps*
and evicting finished ones, so throughput stays at batch-decode levels while
per-request latency stays at single-request levels.

TPU-first shape discipline: everything the device sees is static.

- The KV cache is a ``(num_slots, heads, max_len, head_dim)`` pytree allocated
  once. A request occupies one slot; its cache rows are dense in ``[0, len)``.
- Each slot decodes at its OWN position: the decode step passes ``position`` as
  a ``(num_slots,)`` vector and the model scatters each row's K/V into its own
  column (see ``DecoderBlock`` per-row positions, ``models/gpt.py``). No global
  column counter, no gaps, no compaction; a freed slot is reusable immediately
  because a new request's mask (``k_pos <= position_r``) never reaches stale
  columns before its own decode overwrites them.
- Prefill is BATCHED: queued prompts sharing a bucket prefill together, up to
  ``prefill_batch`` rows per device dispatch (one compile per (rows, bucket)
  shape, both ladders bounded), then one scatter per layer copies every row into
  its slot's cache rows — N queued prompts admit in ⌈N/prefill_batch⌉ prefill
  dispatches instead of N.
- Long prompts optionally prefill in CHUNKS (``prefill_chunk``): one chunk of
  the prompt runs per engine tick, interleaved between decode steps, so a
  512-token prompt never stalls the in-flight decode batch for its whole
  prefill.
- PREFIX CACHING (``prefix_cache_blocks``): completed prompts index their KV
  into a device-side block pool behind a host radix tree
  (:mod:`unionml_tpu.serving.prefix_cache`); an admitted prompt's longest
  cached prefix is restored with one shard-local gather instead of recomputed,
  and only the uncovered suffix runs through prefill — under shared-prefix
  traffic (system prompts, few-shot templates, chat history) prefill FLOPs
  drop by the shared fraction while outputs stay token-identical.
- The decode step jit-compiles exactly once per engine (all shapes fixed).
- PIPELINED DECODE (``pipeline=True``, default): slot lifecycle (``active``,
  ``remaining``) lives ON DEVICE and retires *inside* the compiled step, so
  each tick dispatches step N+1 *before* blocking on step N's token fetch —
  the host applies tokens, admits requests, and fans out events while the
  device runs the next step, instead of the device idling behind every
  ``device_get``. Outputs are token-identical to the unpipelined engine;
  ``cancel``/``abort_all`` flush or discard the in-flight step so slot reuse
  can never misattribute a stale token.

Mesh-sharded serving (``mesh=``): the engine lays the model parameters out with
the GPT family's Megatron-style ``param_shardings`` table and shards the KV
cache over attention HEADS on the mesh's ``tensor`` axis, so ONE compiled decode
step (and one compiled prefill) runs tensor-parallel across every device of the
mesh — XLA inserts the all-reduces over ICI. Outputs are token-identical to the
single-device engine; scheduling, admission, and the HTTP surface above are
unchanged.

``DecodeEngine`` is the synchronous core (useful directly in scripts/tests);
``ContinuousBatcher`` runs it on a worker thread behind an asyncio API for the
serving app's ``/generate`` route — admission no longer runs off a bare FIFO
deque but through the SLO scheduler (:mod:`unionml_tpu.serving.scheduler`):
priority classes with anti-starvation aging, a bounded queue that sheds with
structured errors, deadline enforcement on queued and running requests, and
preempt-to-prefix-cache (:meth:`DecodeEngine.preempt`) that checkpoints a
low-priority victim's KV into the PR-2 radix cache so a higher-priority
arrival gets its slot and the victim resumes for one suffix prefill.

FAULT TOLERANCE (ISSUE 7): the engine fails *well*. A device-side failure
captures every live slot's salvage (host transcript + pinned radix path),
rebuilds the device state in place from host-retained params — with PRNG
continuity, so resumed sampled streams stay bit-identical — and a supervised
batcher (:mod:`unionml_tpu.serving.supervisor`) re-queues every salvageable
request to resume token-identically, paying only a suffix prefill over its
pinned blocks. NaN/Inf logits quarantine the one poisoned slot (an in-program
finiteness flag rides the fused token fetch) instead of failing the batch; a
single request's prefill death rolls admission back atomically and fails only
that request. Every failure a consumer sees is a structured
:class:`~unionml_tpu.serving.faults.EngineFailure` with a machine-readable
reason, and all of it is deterministically injectable via
:class:`~unionml_tpu.serving.faults.FaultPlan` (see ``tests/unit/test_chaos.py``).
"""

import asyncio
import dataclasses
import functools
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from unionml_tpu._logging import logger
from unionml_tpu.profiling import PhaseTimeline
from unionml_tpu.serving.faults import EngineFailure, FaultPlan

#: default prompt-prefill bucket lengths (right-padded; one XLA compile each)
DEFAULT_PREFILL_BUCKETS = (16, 32, 64, 128, 256, 512)


def block_demand(prompt_len: int, budget: int, *, max_len: int, block_size: int) -> int:
    """Pool blocks one request needs for its whole lifetime: prompt plus
    budget, capped at cache capacity, rounded up to whole blocks.

    This is THE paged-admission arithmetic, split out as a pure function so
    the fleet simulator (``unionml_tpu.sim``) gates its virtual admissions on
    the identical math the live batcher uses —
    :meth:`DecodeEngine.block_demand` delegates here."""
    need = min(int(prompt_len) + int(budget), int(max_len))
    return -(-need // int(block_size))


def bind_serving_mesh(model: Any, mesh: Optional[Any]) -> Any:
    """A copy of ``model`` whose config carries the serving ``mesh`` — how the
    paged attention kernel learns, at trace time, the mesh it must
    ``shard_map`` over (see ``GPTConfig.tp_mesh``). The caller's model is left
    alone; models whose config has no such field pass through."""
    if mesh is None or not hasattr(model.config, "tp_mesh"):
        return model
    return model.clone(config=dataclasses.replace(model.config, tp_mesh=mesh))


#: the serving loop's phases (spans ``loop.<phase>``, counters in
#: ``pipeline_stats()["phases"]``): ``idle`` no active slot and nothing queued;
#: ``admit`` deadlines, preemption, scheduler pop, validation, block-demand
#: gate, registration; ``prefill`` padded rows, block allocation, the wave's
#: upload and dispatch, activation; ``plan`` pending events, headroom and
#: lookahead planning; ``dispatch`` enqueueing the decode program;
#: ``fetch_wait`` the host blocked in the fused token fetch (slack, not work);
#: ``apply`` tokens into the host mirrors; ``fan_out`` delivery to the sinks
LOOP_PHASES = ("idle", "admit", "prefill", "plan", "dispatch", "fetch_wait", "apply", "fan_out")

#: a paged admission wave's one upload is an int32 array of a row a request:
#: the padded prompt, then these per-row scalars (slot, prompt length, token
#: budget, ``top_k``, and the bits of the float32 ``temperature`` and
#: ``top_p``), then the slot's block-table row
_WAVE_SCALARS = 6


@dataclasses.dataclass(frozen=True)
class StepEvent:
    """One slot's outcome for one engine step."""

    slot: int
    token: int
    #: False for an EOS token (consumed, not part of the completion)
    emit: bool
    finished: bool
    #: time the request spent queued before admission (ms), attached to the
    #: request's FIRST decoded token only — lets a TTFT measurement decompose
    #: into queue wait vs prefill+decode (None on every later event, and for
    #: requests admitted without a queue, e.g. direct ``add_request`` calls)
    queue_wait_ms: Optional[float] = None
    #: machine-readable failure slug when the ENGINE terminated this request
    #: (``nan_logits`` quarantine, ``prefill_failed`` chunked-prefill death):
    #: the event carries no token (``emit=False``, ``finished=True``) and the
    #: consumer must fail, not finish, the request
    error: Optional[str] = None


@dataclasses.dataclass
class PreemptedSlot:
    """A preempted request's resumable checkpoint (:meth:`DecodeEngine.preempt`).

    ``tokens`` is the slot's full transcript — prompt plus every token decoded
    so far — which becomes the resume prompt; ``path`` is the radix-tree node
    chain holding the transcript's KV blocks, PINNED against LRU eviction
    until :meth:`DecodeEngine.release_preempted` (called after the resume
    re-admission acquired its own references, or when the request is
    cancelled while re-queued)."""

    tokens: List[int]
    path: List[Any]


@dataclasses.dataclass
class SalvagedSlot:
    """One slot's resumable state captured at engine-failure time.

    Unlike :class:`PreemptedSlot` (a deliberate checkpoint that device-copies
    the transcript's KV into the pool first), salvage is captured while the
    device state may be POISONED, so it is host-only: ``tokens`` is the slot's
    replayed transcript (prompt + every token already delivered), ``path`` is
    whatever radix-tree chain the slot already held — pinned, it survives the
    rebuild and shrinks the resume to a suffix prefill — and ``remaining`` is
    the slot's unspent token budget. The collector must eventually unpin
    ``path`` (:meth:`DecodeEngine.release_preempted` accepts the same shape).
    """

    slot: int
    tokens: List[int]
    path: List[Any]
    remaining: int


class DecodeEngine:
    """Slot-based continuous-batching decode engine over a GPT-style model.

    :param model: a :class:`~unionml_tpu.models.gpt.GPTLMHeadModel` or a
        :class:`~unionml_tpu.models.latent_moe.LatentMoELMHeadModel` (anything
        with ``.config``, ``.apply(variables, ids, cache=, position=,
        logit_rows=)`` matching their incremental and paged contract, and
        ``.cache_layout()``: the
        dense cache, the block pool, their sharding and bytes and the paged
        kernel's shape key come from it, so a latent cache of one row a token
        is served by the same tables, allocator and programs as per-head K/V.
        A layout may also keep per-slot state that is not paged
        (``slot_state``: :class:`~unionml_tpu.models.phi4flash.HybridCacheLayout`'s
        recurrent state and window ring): the pool then holds those leaves
        beside the blocks, the admission wave writes them
        (``insert_slot_state``), a prefill chunk is told its slot
        (``cache["slots"]``) and the decode step updates them in place; the
        prefix cache, preemption, speculative decoding, an int8 pool and a
        mesh are refused by name for such a layout).
    :param variables: trained model variables (``{"params": ...}``).
    :param num_slots: concurrent sequences held on device (the decode batch).
    :param max_len: per-slot cache capacity (prompt + generated tokens). A slot
        force-finishes when its length reaches ``max_len - 1``.
    :param eos_token_id: token that terminates a completion (not emitted).
    :param temperature: 0 = greedy (exactly reproduces
        :func:`unionml_tpu.models.gpt.generate` row by row); > 0 samples — note
        sampled streams depend on engine scheduling order, unlike ``generate``.
    :param prefill_buckets: allowed padded prompt lengths; prompts longer than the
        largest bucket (or ``max_len``) are rejected with ``ValueError``.
    :param quantize: ``"int8"`` stores matmul kernels as per-channel int8
        (:mod:`unionml_tpu.ops.quant`) — single-token decode is HBM-bandwidth
        bound, so int8 weights halve the per-step weight traffic vs bf16;
        dequantization happens inside the compiled step and fuses into the
        matmuls. ``None`` (default) serves full-precision weights.
    :param mesh: a ``jax.sharding.Mesh`` (see :mod:`unionml_tpu.parallel.mesh`)
        for tensor-parallel serving: parameters shard Megatron-style
        (:func:`unionml_tpu.models.gpt.param_shardings`), the KV cache shards
        over attention heads on the ``tensor`` axis, and every compiled step runs
        across all mesh devices. ``None`` (default) serves single-device.
    :param prefill_batch: max prompts prefilled per device dispatch — queued
        prompts sharing a bucket admit together, ⌈N/prefill_batch⌉ dispatches
        for N prompts (one compile per (rows, bucket) shape).
    :param prefill_chunk: when set, prompts longer than this prefill in chunks of
        this many tokens, ONE chunk per engine tick between decode steps, so a
        long prompt cannot stall in-flight decodes for its whole prefill. A
        prompt's last chunk is padded to the smallest of ``prefill_buckets``
        that holds it (one compile per such width), not to a whole chunk.
    :param prefix_cache_blocks: when > 0, enable PREFIX CACHING with a device
        KV block pool of this many blocks (see :meth:`enable_prefix_cache`):
        completed prompts index their KV block-by-block into a host radix tree
        (:class:`~unionml_tpu.serving.prefix_cache.PrefixCache`), and admission
        restores each prompt's longest cached prefix instead of recomputing it
        — only the uncovered suffix prefills. ``0`` (default) disables caching.
    :param prefix_block_size: tokens per cached KV block (match granularity and
        pool-copy unit); prefixes match in whole blocks only.
    :param prefix_cache_generated: also index a retiring slot's GENERATED
        tokens' KV, so a multi-turn follow-up prompt (previous prompt +
        completion + new text) hits the whole previous turn, not just its
        prompt.
    :param pipeline: depth-1 PIPELINED decode (default on): each :meth:`step`
        dispatches the next device step *before* fetching the previous step's
        tokens, so the host applies tokens / admits requests while the device
        runs — the device never idles waiting for host scheduling. Legal
        because slot lifecycle (``active``/``remaining``) lives on device and
        retires *inside* the compiled step; outputs are token-identical to
        ``pipeline=False`` (events are simply delivered one tick later).
        ``cancel``/``abort_all``/``reset`` flush or discard the in-flight
        step, so no stale token is ever applied to a reused slot.
    :param paged: PAGED KV decode (default on): the block pool is the ONLY KV
        storage — a slot's "cache" is an int32 block-table row plus a length,
        attention gathers K/V through the table inside the compiled step, and
        decode writes each new token into the slot's tail block in place.
        Admission allocates ``ceil(min(prompt+budget, max_len)/block_size)``
        blocks instead of reserving a dense ``max_len`` row, so concurrency is
        bounded by LIVE tokens, not worst-case length; exhaustion raises the
        structured ``EngineFailure(reason="pool_exhausted", retryable=True)``.
        Prefix-cache hits splice shared pool blocks straight into the table
        (no restore copy) and retiring slots index their blocks by adoption
        (no save copy). Outputs are token-identical to ``paged=False``: the
        gathered table is a contiguous logical view, masked columns contribute
        exactly zero, and the engine's scheduling is unchanged. ``False``
        selects the legacy dense per-slot caches (a comparison arm: only
        tests pass it).
    :param pool_blocks: total pool size in blocks for paged mode (including
        one reserved scratch block that absorbs retired rows' masked writes).
        Default ``None`` sizes the pool so block admission can never fail when
        a slot is free — ``num_slots * ceil(max_len/block_size) +
        prefix_cache_blocks + 1`` — i.e. dense-equivalent capacity semantics;
        pass an explicit smaller value to serve more concurrent short requests
        than dense could at the same KV byte budget
        (``test_small_pool_serves_more_concurrent_requests``).
    :param kv_quantize: ``"int8"`` stores the paged block pool as symmetric
        int8 with per-block-per-head f32 scales resident alongside (see
        :func:`unionml_tpu.models.gpt.init_block_pool`) — int8 is what crosses
        HBM on every decode gather, so a fixed byte budget holds ~2× the
        blocks of a bf16 pool. All writes quantize in-program (prefill insert,
        chunk prefill, the in-place decode append) and the gather dequantizes
        inside the same compiled step; allocation/splice/adopt/preempt move
        block IDs only, so the scheduler is oblivious. Requires ``paged=True``.
        Quality is budgeted, not bit-exact: see the pinned
        ``KV_INT8_*_BUDGET`` constants in :mod:`unionml_tpu.ops.quant`.
    :param kv_quantize_skip_layers: layer indices whose pool stays full
        precision (outlier-sensitive layers); their leaves simply carry no
        scale arrays, which is how the attention layer detects the mode.
    :param faults: a :class:`~unionml_tpu.serving.faults.FaultPlan` arming
        deterministic fault injection (chaos tests only). ``None``
        (production) makes every hook a single host
        branch — no device work, no host syncs added to the hot path.
    """

    def __init__(
        self,
        model: Any,
        variables: Any,
        *,
        num_slots: int = 8,
        max_len: Optional[int] = None,
        eos_token_id: Optional[int] = None,
        temperature: float = 0.0,
        prefill_buckets: Sequence[int] = DEFAULT_PREFILL_BUCKETS,
        seed: int = 0,
        quantize: Optional[str] = None,
        mesh: Optional[Any] = None,
        prefill_batch: int = 4,
        prefill_chunk: Optional[int] = None,
        prefix_cache_blocks: int = 0,
        prefix_block_size: int = 16,
        prefix_cache_generated: bool = False,
        pipeline: bool = True,
        paged: bool = True,
        pool_blocks: Optional[int] = None,
        kv_quantize: Optional[str] = None,
        kv_quantize_skip_layers: Sequence[int] = (),
        faults: Optional[FaultPlan] = None,
        telemetry: Optional[Any] = None,
    ) -> None:
        model = bind_serving_mesh(model, mesh)
        config = model.config
        #: the model's cache layout: dense cache, block pool, their sharding
        #: and bytes, the paged kernel's shape key (per-head K/V for the GPT
        #: family, one latent row a token for ``latent_moe``)
        layout = self._layout = model.cache_layout()
        max_len = max_len or config.max_position_embeddings
        if max_len > config.max_position_embeddings:
            raise ValueError(
                f"max_len ({max_len}) exceeds max_position_embeddings "
                f"({config.max_position_embeddings})"
            )
        if quantize not in (None, "int8"):
            raise ValueError(f"Unknown quantize mode {quantize!r}; expected None or 'int8'")
        if kv_quantize not in (None, "int8"):
            raise ValueError(f"Unknown kv_quantize mode {kv_quantize!r}; expected None or 'int8'")
        if kv_quantize is not None and not paged:
            raise ValueError("kv_quantize requires paged=True (the block pool is what quantizes)")
        if layout.slot_state:
            # what a slot keeps beside its blocks under the table is in no block:
            # every mechanism that moves, shares or re-scales blocks and nothing
            # else is refused by name, not left to be silently wrong
            held = " and ".join(layout.slot_state)
            refused = {
                "prefix_cache_blocks > 0 (the radix prefix cache)": bool(prefix_cache_blocks),
                f"kv_quantize={kv_quantize!r} (an int8 pool)": kv_quantize is not None,
                "mesh= (a device mesh)": mesh is not None,
                "paged=False (dense slot caches)": not paged,
            }
            for what, asked in refused.items():
                if asked:
                    raise ValueError(
                        f"{what} with a cache layout that keeps per-slot {held}: only its blocks "
                        f"under the table could follow, the {held} cannot yet"
                    )
        # quantize + mesh compose: quantization happens first (below), then
        # param_shardings assigns the int8 tree's {q, scale} leaves their specs
        # (the scale inherits the kernel's channel-axis split) and place_by_specs
        # lays the QuantizedArray nodes onto the mesh like any other leaf
        if quantize == "int8":
            from unionml_tpu.ops.quant import dequantize_tree, quantize_tree

            variables = quantize_tree(variables)
            maybe_dequant = dequantize_tree
        else:
            maybe_dequant = lambda tree: tree

        self._mesh = mesh
        self._cache_sharding = None
        self._replicated = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            from unionml_tpu.models._sharding import place_by_specs
            from unionml_tpu.models.gpt import param_shardings
            from unionml_tpu.parallel.mesh import TENSOR_AXIS

            spec_tree = param_shardings(variables, tuple(mesh.axis_names))
            variables = place_by_specs(variables, mesh, spec_tree)
            cache_spec = layout.cache_spec(tuple(mesh.axis_names))
            tensor_size = int(mesh.shape[TENSOR_AXIS]) if TENSOR_AXIS in mesh.axis_names else 1
            if layout.kv_heads % max(tensor_size, 1) != 0:
                cache_spec = PartitionSpec()  # heads don't divide: replicate the cache
            self._cache_sharding = NamedSharding(mesh, cache_spec)
            self._replicated = NamedSharding(mesh, PartitionSpec())

        self._model = model
        self._variables = variables
        self._config = config
        self.num_slots = num_slots
        self.max_len = max_len
        self.eos_token_id = eos_token_id
        self.temperature = float(temperature)
        self.prefill_batch = max(1, int(prefill_batch))
        self.prefill_chunk = int(prefill_chunk) if prefill_chunk else None
        if self.prefill_chunk is not None and self.prefill_chunk < 1:
            raise ValueError(f"prefill_chunk must be >= 1, got {prefill_chunk}")
        # a bucket equal to max_len is fine: prompts are < max_len and the padded
        # prefill occupies exactly the slot's cache columns
        self._buckets = tuple(sorted(b for b in prefill_buckets if b <= max_len)) or (max_len - 1,)

        self._seed = seed
        self._resets = 0

        #: deterministic fault-injection script (None in production: every
        #: hook is a single host ``is not None`` branch — zero device work)
        self._faults = faults
        #: span/metrics collector (None = tracing off: every hook is the same
        #: single host ``is not None`` branch as the fault hooks — no device
        #: work, no host syncs; decode timing reuses the fused-fetch stamps)
        self._telemetry = telemetry
        if faults is not None and telemetry is not None and faults.telemetry is None:
            faults.telemetry = telemetry
        #: slot -> request_id of the occupant's trace (batcher-set); spans for
        #: a slot emitted before the id binds are buffered and flushed at bind
        self._slot_rid: Dict[int, str] = {}
        self._slot_pending_spans: Dict[int, List[Tuple[str, float, Optional[float], Dict[str, Any]]]] = {}
        #: engine-failure incidents survived (the batcher keys recovery off a
        #: delta of this counter, like the old ``_resets`` check but precise)
        self.failure_count = 0
        #: device-state rebuilds performed (in-place recovery + supervised)
        self.rebuilds = 0
        #: requests terminated by per-slot NaN/Inf-logits quarantine
        self.quarantined_requests = 0
        #: salvage captured at the last failure, awaiting :meth:`take_salvage`
        self._salvage: List[SalvagedSlot] = []  # holds: kv-pin
        #: set when an in-place rebuild itself failed: the engine refuses work
        #: until :meth:`rebuild` succeeds (the supervisor retries with backoff;
        #: unsupervised callers retry lazily via ``_ensure_usable``)
        self._failed = False
        #: set by a donating dispatch that raised (its donated engine state is
        #: poisoned); the public entry points escalate to a full failure
        self._device_poisoned = False
        #: key-consuming steps replayed since the key's base was (re)seeded —
        #: lets a resume-rebuild reconstruct the PRNG stream so recovered
        #: sampled requests stay token-identical to a fault-free run
        self._key_steps = 0
        #: liveness timestamp (monotonic) the supervisor's watchdog reads:
        #: refreshed at every step dispatch and token-fetch completion
        self.last_heartbeat = time.monotonic()
        #: slots admitted by the admit_many call in progress (rollback set for
        #: its atomic non-poisoning unwind); None outside admission
        self._admitting: Optional[List[int]] = None

        # host mirrors (authoritative for scheduling; device arrays follow them)
        self._active = np.zeros(num_slots, dtype=bool)
        #: slots holding an in-progress chunked prefill: not active (no decode
        #: yet), not free (their cache rows are being written)
        self._reserved = np.zeros(num_slots, dtype=bool)
        self._partials: Dict[int, Dict[str, Any]] = {}
        self._lens_host = np.zeros(num_slots, dtype=np.int64)
        self._remaining = np.zeros(num_slots, dtype=np.int64)
        # per-slot sampling controls (requests may override the engine defaults)
        self._slot_temp = np.full(num_slots, self.temperature, dtype=np.float32)
        self._slot_top_k = np.zeros(num_slots, dtype=np.int32)
        self._slot_top_p = np.ones(num_slots, dtype=np.float32)
        #: device dispatches spent on prefill since construction (admission
        #: batching makes this ⌈N/prefill_batch⌉ per N same-bucket prompts)
        self.prefill_dispatches = 0
        #: REAL prompt tokens run through prefill compute (padding excluded);
        #: prefix-cache hits shrink this to the uncovered suffix per request —
        #: the FLOP counter the prefix-cache tests assert on
        self.prefill_tokens_computed = 0
        #: pool→slot prefix restores / slot→pool block saves dispatched
        self.prefix_restore_dispatches = 0
        self.prefix_save_dispatches = 0

        #: depth-1 pipelining: dispatch step N+1 before fetching step N's tokens
        self.pipeline = bool(pipeline)
        #: the dispatched-but-unfetched step: ``(tokens, masks, bads, n_steps,
        #: counts)``, device arrays (leading axis = steps in the burst) around the
        #: burst's length, or None when drained
        self._inflight: Optional[Tuple[Any, Any, Any, int, Dict[str, Any]]] = None
        #: slots QUARANTINED while ``_inflight`` was already dispatched: that
        #: burst still carries their (garbage) tokens under an active mask, so
        #: its replay must skip them — the slot may hold a NEW occupant by
        #: then, and crediting the stale token would corrupt its stream (the
        #: same hazard cancel() avoids by flushing first, which a quarantine —
        #: raised DURING a replay — cannot)
        self._inflight_skip: set = set()
        #: events replayed by an out-of-band flush (cancel/admission), delivered
        #: by the next :meth:`step` so the batcher's fan-out sees every token
        self._pending_events: List[StepEvent] = []
        #: lifetime generation counters (the /stats surface both generator
        #: kinds share — see serving.app and serving.speculative)
        self.requests_admitted = 0
        self.tokens_decoded = 0
        #: running slots checkpointed into the prefix cache by :meth:`preempt`
        self.preempted_requests = 0
        #: per-slot queue wait (ms) noted by the batcher at admission
        #: (:meth:`note_queue_wait`); attached to the slot's first StepEvent
        self._slot_queue_wait: Dict[int, float] = {}
        #: device-idle accounting: a dispatch is "idle" when the device queue
        #: was empty when it was enqueued (no in-flight step)
        self.step_dispatches = 0
        self.idle_dispatches = 0
        #: sum over dispatches of (host-active slots x steps in the burst):
        #: with ``tokens_decoded`` and ``step_dispatches`` it differences into
        #: a window's occupancy
        self.active_slot_steps = 0
        #: paged engines: the same sum with every active slot weighted by the
        #: table columns its row holds keys in (``lens // block_size + 1``).
        #: Over ``active_slot_steps x table width`` it is the share of the block
        #: table the paged kernel's bounded walk still visits
        self.live_block_steps = 0
        #: paged engines on the kernel: sum over dispatches of the steps one
        #: layer's call of the decode kernel takes (the tiles it fetches and
        #: folds: ``ops.paged_attention.walk_steps`` of every row's position,
        #: a retired row's sentinel among them), x steps of the burst. Over
        #: ``active_slot_steps`` it is kernel steps a decoding row
        self.kernel_grid_steps = 0
        #: paged engines: host-to-device uploads plus program dispatches
        #: issued by bucketed admission waves, counted where each is issued.
        #: Over the ``prefill`` phase's ``entries`` it is calls a wave: 2
        #: (the dense engine's wave, several programs, is not counted)
        self.wave_device_calls = 0
        #: counted by the batcher's fan-out (``ContinuousBatcher._dispatch_events``):
        #: ``sink_handoffs`` +1 a cross-thread call to a consumer's event loop,
        #: ``sink_deliveries`` +1 an item one carried. Their quotient over a
        #: window is deliveries a hand-over: about a step's tokens
        self.sink_handoffs = 0
        self.sink_deliveries = 0
        #: sum over dispatches of (the host-active slots' lengths x steps in the
        #: burst): with ``active_slot_steps`` and ``live_block_steps`` it gives
        #: ``resident_byte_steps`` a live token (:meth:`pipeline_stats`)
        self.live_token_steps = 0
        #: admissions that began from empty per-slot state (every admission of a
        #: layout with ``slot_state``: the wave overwrites it, a first chunk zeroes it)
        self.state_resets = 0
        #: prompt positions that did not enter the layout's ``tail_layers`` (the
        #: layers a prefill runs for the position it reads alone)
        self.cross_rows_skipped = 0
        self._walk: Optional[Tuple[Tuple[int, int], Any]] = None
        #: the model's own step counters (what it sows into its ``"stats"``
        #: collection in a decode step, e.g. a sparse model's ``expert_rows``),
        #: summed over decode steps: fetched with the step's tokens, added in
        #: ``loop.apply``
        self.model_counters: Dict[str, int] = {}
        self._last_fetch_done: Optional[float] = None
        #: what the loop thread that drives this engine is doing (see
        #: :data:`LOOP_PHASES`); the batcher drives the same instance
        self.timeline = PhaseTimeline("loop", LOOP_PHASES)

        # prefix cache (disabled until enable_prefix_cache): host radix index +
        # device KV block pool + per-slot held node paths / token transcripts
        self.prefix_cache: Optional[Any] = None
        self.prefix_cache_generated = bool(prefix_cache_generated)
        self._prefix_block_size = int(prefix_block_size)
        self._pool: Optional[Any] = None
        self._slot_path: Dict[int, List[Any]] = {}
        self._slot_tokens: Dict[int, List[int]] = {}

        #: paged KV decode: the pool is the ONLY KV storage (no dense cache)
        self.paged = bool(paged)
        #: block allocator backing the paged pool; doubles as the radix index
        #: when prefix caching is enabled. None on dense engines.
        self._allocator: Optional[Any] = None
        #: per-slot PRIVATE blocks: block index -> pool block id the slot owns
        #: (shared spliced prefix entries live in _slot_path, not here).
        #: Freeing on retirement is safe even with a step in flight: every
        #: pool WRITE chains through the pool's donation (admission inserts
        #: queue after the in-flight step), and a reused block's new positions
        #: are always written by the new owner before its attention reads them.
        self._slot_block_map: Dict[int, Dict[int, int]] = {}  # holds: kv-block
        self._explicit_pool_blocks = pool_blocks is not None
        #: int8 KV pool mode ("int8" or None) + the layers kept full-precision
        self.kv_quantize = kv_quantize
        self.kv_quantize_skip_layers = tuple(int(i) for i in kv_quantize_skip_layers)
        if any(i < 0 or i >= config.num_layers for i in self.kv_quantize_skip_layers):
            raise ValueError(
                f"kv_quantize_skip_layers {self.kv_quantize_skip_layers} out of range "
                f"for {config.num_layers} layers"
            )
        if self.paged:
            from unionml_tpu.models.gpt import block_table_width
            from unionml_tpu.serving.prefix_cache import PrefixCache

            # the pool's block size IS the prefix cache's block size (one
            # layout, spliced freely); clamp so short-context engines with the
            # default granularity still page
            bs = min(int(prefix_block_size), max_len)
            self._prefix_block_size = bs
            self._table_width = block_table_width(max_len, bs)
            per_slot = self._table_width - 1  # data columns (excludes scratch)
            if pool_blocks is None:
                # dense-equivalent capacity: a free slot can always allocate
                pool_blocks = num_slots * per_slot + int(prefix_cache_blocks) + 1
            if int(pool_blocks) < 2:
                raise ValueError(f"pool_blocks must be >= 2 (1 usable + scratch), got {pool_blocks}")
            self.pool_blocks = int(pool_blocks)
            #: reserved block absorbing retired rows' masked scatter; never allocated
            self._scratch_block = self.pool_blocks - 1
            self._allocator = PrefixCache(
                self.pool_blocks - 1, bs, telemetry=self._telemetry
            )
            # resolve the decode-attention backend ONCE (same shape key the
            # model's dispatcher sees at trace time: full table width + pool
            # block size) so telemetry reports what the traced program runs
            from unionml_tpu.ops.paged_attention import resolve_paged_impl

            self.paged_attn_impl: Optional[str] = resolve_paged_impl(
                getattr(config, "paged_attn_impl", "auto"),
                self._table_width,
                bs,
                *layout.kernel_key,
            )
            if self._telemetry is not None:
                self._telemetry.paged_attn_impl.set(1.0, self.paged_attn_impl)
        else:
            self.paged_attn_impl = None

        self._init_device_state()
        self._sync_sampling_mirrors()

        cache_sharding = self._cache_sharding

        def _constrain_cache(tree):
            # keep the head-sharded layout pinned through every compiled program:
            # propagation alone may let GSPMD re-layout the (donated) cache
            if cache_sharding is None:
                return tree
            return jax.tree_util.tree_map(
                lambda leaf: jax.lax.with_sharding_constraint(leaf, cache_sharding), tree
            )

        def _decode_body(variables, cache, last_logits, lens, active, key, temp, top_k, top_p, *, sampling):
            """One decode step — the single shared body for every step program
            (any burst depth), so sampling/freeze rules cannot drift between them.

            ``sampling`` is a trace-time switch: the all-greedy program skips the
            sort/softmax sampling machinery entirely; the sampling program honors
            per-slot temperature/top-k/top-p (greedy rows via ``temperature == 0``).
            """
            from unionml_tpu.ops.sampling import sample_logits

            # dequant here (not hoisted) so weight reads stay int8 in HBM
            variables = maybe_dequant(variables)
            new_key, subkey = jax.random.split(key)
            # an all-inactive step consumes NO key: pipelining may dispatch one
            # masked step past full retirement, and sampled streams must stay
            # identical to an engine that (knowing the retirement) never ran it
            new_key = jnp.where(jnp.any(active), new_key, key)
            # per-slot finiteness of the logits this step SAMPLES from: a
            # NaN/Inf row (weight corruption, a NaN storm, injected poison)
            # flags only its own slot, rides the fetch with tokens/masks, and
            # quarantines that request host-side — siblings keep decoding
            bad = ~jnp.all(jnp.isfinite(last_logits), axis=-1)
            if sampling:
                tokens = sample_logits(last_logits, subkey, temp, top_k, top_p)
            else:
                tokens = jnp.argmax(last_logits, axis=-1).astype(jnp.int32)
            # the model's step counters: int32 scalars it sows into "stats"
            # (a model that sows none leaves the collection empty)
            (logits, cache), sown = model.apply(
                variables, tokens[:, None], cache=cache, position=lens, mutable=["stats"]
            )
            counts = dict(sown.get("stats", {}))
            cache = _constrain_cache(cache)
            # inactive rows freeze: length and logits unchanged, their (ignored)
            # cache write lands on a column their own future prefill/decode rewrites
            new_lens = jnp.where(active, jnp.minimum(lens + 1, max_len - 1), lens)
            new_logits = jnp.where(active[:, None], logits[:, -1, :], last_logits)
            return cache, new_logits, new_lens, tokens, new_key, bad, counts

        def _make_step(n_steps: int, sampling: bool):
            """K decode steps fused into one device program (``lax.scan``;
            ``n_steps=1`` is the plain per-tick step).

            The program CARRIES the slot lifecycle: ``active``/``remaining``
            ride as device-resident inputs and retirement (eos / budget / cache
            room — :func:`unionml_tpu.models.gpt.advance_slot_state`) runs
            inside the scan, so the next step can be dispatched before this
            one's tokens are fetched (depth-1 pipelining) and a fused burst
            emits exactly what K sequential steps would. The host replays the
            fetched ``(tokens, masks)`` to update its mirrors identically.
            """
            from unionml_tpu.models.gpt import advance_slot_state

            def _multi(variables, cache, last_logits, lens, active, remaining, key, temp, top_k, top_p):
                def body(carry, _):
                    cache, last_logits, lens, active, remaining, key = carry
                    cache, new_logits, new_lens, tokens, key, bad, counts = _decode_body(
                        variables, cache, last_logits, lens, active, key, temp, top_k, top_p,
                        sampling=sampling,
                    )
                    new_active, new_remaining = advance_slot_state(
                        active, remaining, new_lens, tokens, max_len, eos_token_id
                    )
                    carry = (cache, new_logits, new_lens, new_active, new_remaining, key)
                    return carry, (tokens, active, bad, counts)

                carry = (cache, last_logits, lens, active, remaining, key)
                (cache, last_logits, lens, active, remaining, key), (toks, masks, bads, counts) = (
                    jax.lax.scan(body, carry, None, length=n_steps)
                )
                return cache, last_logits, lens, active, remaining, key, toks, masks, bads, counts

            return jax.jit(_multi, donate_argnums=(1, 2))

        self._make_step = _make_step
        self._step_fns: Dict[Tuple[int, bool], Any] = {}

        def _slot_update(active, remaining, temp, top_k, top_p, slot, is_active, budget, t, k, p):
            """Point-update the device slot mirrors for one admission/cancel —
            ONE tiny dispatch, preserving every other slot's device-side value
            (which may embed retirements from a still-unfetched in-flight step,
            so a full host upload here would be WRONG, not just slow)."""
            return (
                active.at[slot].set(is_active),
                remaining.at[slot].set(budget),
                temp.at[slot].set(t),
                top_k.at[slot].set(k),
                top_p.at[slot].set(p),
            )

        self._slot_update_fn = jax.jit(_slot_update, donate_argnums=(0, 1, 2, 3, 4))

        def _prefill(variables, prompt_ids, lengths):
            """Batched bucket prefill: (rows, bucket) prompts, one device dispatch.

            Rows are right-padded to the shared bucket; causal attention keeps
            each row's logits at its last REAL token unaffected by the padded
            tail (and by the other rows — rows are attention-independent).
            """
            variables = maybe_dequant(variables)
            rows, bucket = prompt_ids.shape
            local_cache = layout.init_cache(rows, bucket)
            idx = jnp.clip(lengths.astype(jnp.int32) - 1, 0, bucket - 1)
            # each row's last real token is the only position whose logits are read
            logits, local_cache = model.apply(
                variables, prompt_ids, cache=local_cache, position=0, logit_rows=idx
            )
            return _constrain_cache(local_cache), logits[:, 0, :]

        self._prefill_fn = jax.jit(_prefill)  # re-traces per (rows, bucket) shape (bounded)

        def _chunk_apply(variables, chunk_ids, local_cache, position, pick):
            """One chunk of a long prefill: attends over the cache prefix written
            by earlier chunks (``position`` is traced — one compile per
            (chunk, cache_len) shape, not per offset). Returns the logits of the
            chunk's token ``pick`` alone, (1, vocab): the one row a final chunk
            seeds decoding from."""
            variables = maybe_dequant(variables)
            logits, local_cache = model.apply(
                variables, chunk_ids, cache=local_cache, position=position,
                logit_rows=jnp.reshape(pick, (1,)),
            )
            return logits[:, 0, :], _constrain_cache(local_cache)

        self._chunk_fn = jax.jit(_chunk_apply, donate_argnums=(2,))

        def _insert(cache, lens, last_logits, local_cache, local_logits, slots, lengths):
            def put(full, local):
                width = local.shape[2]
                return full.at[slots, :, :width, :].set(local.astype(full.dtype))

            cache = jax.tree_util.tree_map(put, cache, local_cache)
            return (
                _constrain_cache(cache),
                lens.at[slots].set(lengths.astype(lens.dtype)),
                last_logits.at[slots].set(local_logits.astype(jnp.float32)),
            )

        self._insert_fn = jax.jit(_insert, donate_argnums=(0, 1, 2))

        def _restore(pool, block_ids, pad_len):
            """Gather cached prefix blocks into a fresh batch-1 local cache
            (columns beyond the prefix zero, written by the suffix prefill).
            The gather indexes the unsharded block axis: shard-local on a mesh."""
            from unionml_tpu.models.gpt import gather_block_prefix

            return _constrain_cache(layout.split(gather_block_prefix(pool, block_ids, pad_len)))

        # one compile per (n_blocks, pad_len) — both from small bounded ladders
        self._restore_fn = jax.jit(_restore, static_argnums=(2,))

        def _save(pool, cache, row, start_block, dst_ids, block_size):
            """Scatter one slot's cache blocks [start, start+n) into the pool at
            ``dst_ids``; row/start are traced (one compile per block count)."""
            from unionml_tpu.models.gpt import slice_cache_blocks

            blocks = layout.join(
                slice_cache_blocks(cache, row, start_block, dst_ids.shape[0], block_size)
            )

            def put(pool_leaf, blk):
                return pool_leaf.at[dst_ids].set(blk.astype(pool_leaf.dtype))

            return _constrain_cache(jax.tree_util.tree_map(put, pool, blocks))

        self._save_fn = jax.jit(_save, static_argnums=(5,), donate_argnums=(0,))

        if self.paged:
            # The paged programs below read self._prefix_block_size /
            # self._table_width at TRACE time, never as __init__-captured
            # locals: enable_prefix_cache can re-lay-out the pool after
            # construction, and any block-size/width change alters the pool
            # leaf and table shapes, forcing every jitted paged program to
            # retrace — which is exactly when the fresh values are re-read.

            def _decode_body_paged(
                variables, pool, tables, last_logits, lens, active, key, temp, top_k, top_p,
                *, sampling,
            ):
                """Paged twin of ``_decode_body``: same sampling/freeze/key
                rules, but K/V reads gather through the block tables and the
                token write scatters into each row's tail block. Tables ride as
                a NON-donated input — they change only at admission, between
                dispatches, so an in-flight step always reads a consistent map."""
                from unionml_tpu.ops.sampling import sample_logits

                variables = maybe_dequant(variables)
                new_key, subkey = jax.random.split(key)
                new_key = jnp.where(jnp.any(active), new_key, key)
                bad = ~jnp.all(jnp.isfinite(last_logits), axis=-1)
                if sampling:
                    tokens = sample_logits(last_logits, subkey, temp, top_k, top_p)
                else:
                    tokens = jnp.argmax(last_logits, axis=-1).astype(jnp.int32)
                # a retired row still scatters one K/V column per step (the
                # program is unmasked); aiming its position at the sentinel
                # (>= (width-1)*block_size maps every masked write to
                # table[:, -1], the trailing scratch column) sends that write
                # to scratch, so a freed block can be re-owned by another slot
                # without this row's stale table corrupting it
                sentinel = (self._table_width - 1) * self._prefix_block_size
                pos = jnp.where(active, lens, sentinel)
                cache = {"table": tables, **pool}
                (logits, new_cache), sown = model.apply(
                    variables, tokens[:, None], cache=cache, position=pos, mutable=["stats"]
                )
                counts = dict(sown.get("stats", {}))
                pool = {name: leaf for name, leaf in new_cache.items() if name != "table"}
                pool = _constrain_cache(pool)
                new_lens = jnp.where(active, jnp.minimum(lens + 1, max_len - 1), lens)
                new_logits = jnp.where(active[:, None], logits[:, -1, :], last_logits)
                return pool, new_logits, new_lens, tokens, new_key, bad, counts

            def _make_step_paged(n_steps: int, sampling: bool):
                """Paged ``_make_step``: identical scan/lifecycle contract; the
                carried KV state is the (donated) pool instead of a dense cache."""
                from unionml_tpu.models.gpt import advance_slot_state

                def _multi(
                    variables, pool, tables, last_logits, lens, active, remaining, key,
                    temp, top_k, top_p,
                ):
                    def body(carry, _):
                        pool, last_logits, lens, active, remaining, key = carry
                        pool, new_logits, new_lens, tokens, key, bad, counts = _decode_body_paged(
                            variables, pool, tables, last_logits, lens,
                            active, key, temp, top_k, top_p, sampling=sampling,
                        )
                        new_active, new_remaining = advance_slot_state(
                            active, remaining, new_lens, tokens, max_len, eos_token_id
                        )
                        carry = (pool, new_logits, new_lens, new_active, new_remaining, key)
                        return carry, (tokens, active, bad, counts)

                    carry = (pool, last_logits, lens, active, remaining, key)
                    (pool, last_logits, lens, active, remaining, key), (toks, masks, bads, counts) = (
                        jax.lax.scan(body, carry, None, length=n_steps)
                    )
                    return pool, last_logits, lens, active, remaining, key, toks, masks, bads, counts

                return jax.jit(_multi, donate_argnums=(1, 3))

            self._make_step = _make_step_paged

            def _paged_insert(pool, table_rows, local_cache, lengths, slots):
                """Write a batched bucket prefill's dense workspace into the
                admitted slots' pool blocks through their table rows, whole
                blocks at a time: the indexed axis leads, so XLA writes in
                place (a column scatter ``.at[dst, :, off, :]`` copied the pool
                whole, three times a leaf). The tail of a row's last block
                holds the prefill's padded columns, which the positional mask
                hides until the decode append overwrites them; blocks past a
                slot's allocation map to scratch (the rows' unmapped tail), so
                the full-precision write needs no per-row length mask.
                Quantized layers DO mask: a padded column landing in an owned
                block must not inflate that block's absmax scale, so positions
                at/after a row's real length quantize as zeros. What the
                layout keeps per slot and not under the table (``slot_state``)
                it writes itself, at ``slots`` (``insert_slot_state``)."""
                # graftlint: disable=retrace -- deliberate trace-time read: block_size is an axis of every pool leaf and fixes the table width, so any host mutation (enable_prefix_cache re-layout) changes this program's input shapes and forces the retrace that re-reads it
                block_size = self._prefix_block_size
                # the workspace's layers under the table, by the pool's names (k beside v in one leaf)
                joined = layout.join(local_cache)
                bucket = jax.tree_util.tree_leaves(joined)[0].shape[2]
                nb = -(-bucket // block_size)
                dst_blocks = table_rows[:, :nb]  # (rows, nb)
                valid = (
                    jnp.arange(nb * block_size).reshape(nb, block_size)[None, :, :]
                    < lengths[:, None, None]
                )  # (rows, nb, bs)

                def as_blocks(local_leaf):
                    # (rows, heads, bucket, dim) -> (rows, nb, heads, bs, dim), zeros past the bucket
                    rows, heads, _, dim = local_leaf.shape
                    src = jnp.pad(local_leaf, ((0, 0), (0, 0), (0, nb * block_size - bucket), (0, 0)))
                    return src.reshape(rows, heads, nb, block_size, dim).transpose(0, 2, 1, 3, 4)

                def put_full(pool_leaf, local_leaf):
                    return pool_leaf.at[dst_blocks].set(as_blocks(local_leaf).astype(pool_leaf.dtype))

                def put_quantized(pool_q, pool_scale, local_leaf):
                    from unionml_tpu.ops.quant import quantize_blockwise

                    # block layout, padded tail zeroed
                    src = jnp.where(
                        valid[:, :, None, :, None], as_blocks(local_leaf.astype(jnp.float32)), 0.0
                    )
                    q, scale = quantize_blockwise(src, reduce_axes=(3, 4))
                    return pool_q.at[dst_blocks].set(q), pool_scale.at[dst_blocks].set(scale)

                new_pool = dict(pool)
                for name in joined:
                    layer = pool[name]
                    if "k_scale" in layer:
                        out = {}
                        for key in ("k", "v"):
                            out[key], out[key + "_scale"] = put_quantized(
                                layer[key], layer[key + "_scale"], local_cache[name][key]
                            )
                        new_pool[name] = out
                    else:
                        new_pool[name] = {
                            key: put_full(leaf, joined[name][key]) for key, leaf in layer.items()
                        }
                new_pool = layout.insert_slot_state(new_pool, local_cache, slots, lengths)
                return _constrain_cache(new_pool)

            def _prefill_wave(
                variables, pool, tables, lens, last_logits, active, remaining, temp, top_k, top_p, wave
            ):
                """One bucketed admission wave, whole: the rows' table rows
                written, the bucket prefill, its workspace scattered through
                those rows into the pool, and ``lens``, ``last_logits`` and the
                five slot mirrors set at the rows' slots. ``wave`` is the
                wave's one upload (:meth:`_dispatch_wave` packs it).
                Everything but the weights and ``wave`` is DONATED; an
                in-flight step keeps the old ``tables`` and mirror arrays, as
                under ``_write_row`` and ``_slot_update``."""
                # graftlint: disable=retrace -- deliberate trace-time read: the table width is an axis of ``tables``, so a host mutation (enable_prefix_cache re-layout) changes this program's input shapes and forces the retrace that re-reads it
                bucket = wave.shape[1] - _WAVE_SCALARS - self._table_width
                prompt_ids = wave[:, :bucket]
                slots, lengths, budgets, ks, ts, ps = (
                    wave[:, bucket + i] for i in range(_WAVE_SCALARS)
                )
                table_rows = wave[:, bucket + _WAVE_SCALARS:]
                local_cache, local_logits = _prefill(variables, prompt_ids, lengths)
                return (
                    _paged_insert(pool, table_rows, local_cache, lengths, slots),
                    tables.at[slots].set(table_rows),
                    lens.at[slots].set(lengths),
                    last_logits.at[slots].set(local_logits.astype(jnp.float32)),
                    active.at[slots].set(True),
                    remaining.at[slots].set(budgets),
                    temp.at[slots].set(jax.lax.bitcast_convert_type(ts, jnp.float32)),
                    top_k.at[slots].set(ks),
                    top_p.at[slots].set(jax.lax.bitcast_convert_type(ps, jnp.float32)),
                )

            self._prefill_wave_fn = jax.jit(_prefill_wave, donate_argnums=tuple(range(1, 10)))

            def _paged_chunk(variables, chunk_ids, pool, tables, slot, position, pick):
                """One batch-1 prefill chunk written STRAIGHT into the slot's
                pool blocks through its table row (no local workspace): this is
                both the chunked-prefill tick and the prefix-hit suffix — the
                matched prefix is already pool-resident behind the same table,
                so attending over the gathered row IS the copy-free restore.
                Returns the logits of the chunk's token ``pick`` alone, (1,
                vocab): the one row a final chunk seeds decoding from (every
                queued chunk would otherwise hold a chunk x vocab array)."""
                variables = maybe_dequant(variables)
                row = jax.lax.dynamic_slice_in_dim(tables, slot, 1, axis=0)  # (1, width)
                # "slots": whose chunk this is, for a layout that keeps per-slot state
                cache = {"table": row, "slots": jnp.reshape(slot, (1,)), **pool}
                logits, new_cache = model.apply(
                    variables, chunk_ids, cache=cache, position=position,
                    logit_rows=jnp.reshape(pick, (1,)),
                )
                pool = {name: leaf for name, leaf in new_cache.items() if name != "table"}
                return logits[:, 0, :], _constrain_cache(pool)

            self._paged_chunk_fn = jax.jit(_paged_chunk, donate_argnums=(2,))

            def _write_row(tables, slot, row):
                """Point-update one slot's table row at admission (explicit
                device_put operands; the in-flight step keeps the OLD tables
                array, so this is pipelining-safe like _slot_update)."""
                return tables.at[slot].set(row)

            self._write_row_fn = jax.jit(_write_row, donate_argnums=(0,))

            def _finish_slot(lens, last_logits, slot, length, last):
                """Seal a table-resident prefill (chunked final tick / prefix
                suffix): the KV is already in the slot's blocks, only the
                length and sampling logits need the point-update."""
                return (
                    lens.at[slot].set(length),
                    last_logits.at[slot].set(last[0].astype(jnp.float32)),
                )

            self._finish_slot_fn = jax.jit(_finish_slot, donate_argnums=(0, 1))

        if prefix_cache_blocks:
            self.enable_prefix_cache(
                prefix_cache_blocks, prefix_block_size, cache_generated=prefix_cache_generated
            )

    # ------------------------------------------------------------------ scheduling

    def _init_device_state(self) -> None:
        """(Re)allocate the device-side state, laid out on the mesh when sharded.

        Paged mode allocates the block pool + per-slot block tables instead of
        the dense per-slot cache — the pool is the ONLY KV storage, so this is
        also where a rebuild discards a poisoned pool (the step donates it)."""
        from unionml_tpu.models.gpt import init_block_tables, init_slot_state

        if self.paged:
            self._cache = None
            pool = self._layout.init_block_pool(
                self.pool_blocks,
                self._prefix_block_size,
                kv_quantize=self.kv_quantize,
                kv_quantize_skip_layers=self.kv_quantize_skip_layers,
                num_slots=self.num_slots,
            )
            tables = init_block_tables(
                self.num_slots, self.max_len, self._prefix_block_size, self._scratch_block
            )
        else:
            self._cache = self._layout.init_cache(self.num_slots, self.max_len)
        lens = jnp.zeros((self.num_slots,), jnp.int32)
        last_logits = jnp.zeros((self.num_slots, self._config.vocab_size), jnp.float32)
        key = jax.random.PRNGKey(self._seed + self._resets)
        active, remaining = init_slot_state(self.num_slots)
        if self._mesh is not None:
            if self.paged:
                pool = jax.device_put(pool, self._cache_sharding)
                tables = jax.device_put(tables, self._replicated)
            else:
                self._cache = jax.device_put(self._cache, self._cache_sharding)
            lens = jax.device_put(lens, self._replicated)
            last_logits = jax.device_put(last_logits, self._replicated)
            key = jax.device_put(key, self._replicated)
            active = jax.device_put(active, self._replicated)
            remaining = jax.device_put(remaining, self._replicated)
        if self.paged:
            self._pool, self._tables = pool, tables
        self._lens, self._last_logits, self._key = lens, last_logits, key
        self._active_dev, self._remaining_dev = active, remaining
        # any dispatched-but-unfetched step referenced the old buffers: dead now
        self._inflight = None
        self._inflight_skip = set()

    def _sync_sampling_mirrors(self) -> None:
        """Refresh the device mirrors of the per-slot sampling controls from the
        host arrays — a FULL upload, so callable only when no step is in flight
        (construction, :meth:`reset`, :meth:`abort_all`); per-admission changes
        go through the point-update path in :meth:`_activate` instead.
        """
        # under a mesh the mirrors live replicated on it, like every other piece
        # of slot state (``_replicated`` is None without one: the default device)
        self._temp_dev, self._top_k_dev, self._top_p_dev = jax.device_put(
            (self._slot_temp, self._slot_top_k, self._slot_top_p), self._replicated
        )

    def _sync_slot_mirrors(self) -> None:
        """Re-upload the device slot lifecycle (``active``/``remaining``) from
        the host arrays. Same full-upload caveat as the sampling mirrors: the
        host view lags a dispatched step, so callers must have flushed or
        discarded the pipeline first."""
        active = jnp.asarray(self._active)
        remaining = jnp.asarray(
            np.minimum(self._remaining, np.iinfo(np.int32).max), dtype=jnp.int32
        )
        if self._mesh is not None:
            active = jax.device_put(active, self._replicated)
            remaining = jax.device_put(remaining, self._replicated)
        self._active_dev, self._remaining_dev = active, remaining

    def enable_prefix_cache(
        self, num_blocks: int, block_size: int = 16, *, cache_generated: bool = False
    ) -> None:
        """Allocate the prefix cache: a host radix index over token-id blocks
        plus a device KV block pool of ``num_blocks`` blocks of ``block_size``
        tokens, laid out with the slot cache's head-sharded spec under a mesh
        (pool↔slot copies stay shard-local). ``cache_generated`` also indexes a
        retiring slot's generated tokens for multi-turn reuse. Callable once,
        either via the constructor (``prefix_cache_blocks=``) or after
        construction (serving-app plumbing)."""
        from unionml_tpu.serving.prefix_cache import PrefixCache

        if self.prefix_cache is not None:
            raise RuntimeError("prefix cache is already enabled on this engine")
        if self._layout.slot_state:
            raise ValueError(
                "the radix prefix cache with a cache layout that keeps per-slot "
                f"{' and '.join(self._layout.slot_state)}: a shared prefix's blocks hold its keys, "
                "and the state at its end is in no block"
            )
        block_size = int(block_size)
        if not 1 <= block_size < self.max_len:
            raise ValueError(
                f"prefix_block_size must be in [1, max_len) = [1, {self.max_len}), got {block_size}"
            )
        if self.paged:
            # the allocator IS the index: indexing just turns on over the same
            # pool the slots already page through. A post-construction call
            # (serving-app plumbing) may change the block size / add headroom,
            # which re-lays-out the pool — only legal while nothing is held.
            from unionml_tpu.models.gpt import block_table_width

            width = block_table_width(self.max_len, block_size)
            pool_blocks = self.pool_blocks
            if not self._explicit_pool_blocks:
                pool_blocks = self.num_slots * (width - 1) + int(num_blocks) + 1
            if block_size != self._prefix_block_size or pool_blocks != self.pool_blocks:
                if self.busy or self._inflight is not None or self._allocator.slot_blocks:
                    raise RuntimeError(
                        "enable_prefix_cache cannot re-layout the block pool while "
                        "requests hold blocks; call it before admitting work"
                    )
                self._prefix_block_size = block_size
                self._table_width = width
                self.pool_blocks = pool_blocks
                self._scratch_block = pool_blocks - 1
                self._allocator = PrefixCache(
                    pool_blocks - 1, block_size, telemetry=self._telemetry
                )
                # the shape-class key changed with the re-layout: re-resolve
                # the decode backend the retraced program will dispatch to
                from unionml_tpu.ops.paged_attention import resolve_paged_impl

                self.paged_attn_impl = resolve_paged_impl(
                    getattr(self._config, "paged_attn_impl", "auto"),
                    width,
                    block_size,
                    *self._layout.kernel_key,
                )
                if self._telemetry is not None:
                    self._telemetry.paged_attn_impl.set(1.0, self.paged_attn_impl)
                self._init_device_state()
                self._sync_sampling_mirrors()
            self.prefix_cache = self._allocator
            self.prefix_cache_generated = bool(cache_generated)
            return
        self.prefix_cache = PrefixCache(int(num_blocks), block_size, telemetry=self._telemetry)
        self.prefix_cache_generated = bool(cache_generated)
        self._prefix_block_size = block_size
        self._pool = self._layout.init_block_pool(int(num_blocks), block_size)
        if self._mesh is not None:
            self._pool = jax.device_put(self._pool, self._cache_sharding)

    @property
    def preemptible(self) -> bool:
        """Whether :meth:`preempt` can checkpoint a running slot: the prefix
        cache is on (a layout with per-slot state refuses it, so such an engine
        never is). The SLO scheduler asks before it picks a victim."""
        return self.prefix_cache is not None

    @property
    def free_slots(self) -> List[int]:
        # reserved slots (chunked prefill in progress) are neither active nor free
        return [int(s) for s in np.flatnonzero(~(self._active | self._reserved))]

    @property
    def num_active(self) -> int:
        return int(self._active.sum())

    @property
    def has_pending_prefill(self) -> bool:
        """Whether any slot holds an in-progress chunked prefill (the engine must
        keep ticking even with zero active decodes)."""
        return bool(self._partials)

    def bucket_for(self, prompt_len: int) -> int:
        for bucket in self._buckets:
            if bucket >= prompt_len:
                return bucket
        raise ValueError(
            f"prompt length {prompt_len} exceeds the largest prefill bucket "
            f"({self._buckets[-1]}); raise prefill_buckets/max_len or truncate"
        )

    def check_prefillable(self, prompt_len: int) -> None:
        """Raise ``ValueError`` unless some prefill path takes a prompt of this
        length: chunks where ``prefill_chunk`` is set, the prompt is longer
        than one and its chunks fit the slot's rows (:meth:`_start_chunked`
        then serves it and asks for no bucket that holds it whole), else the
        bucket ladder."""
        chunk = self.prefill_chunk
        if chunk is not None and prompt_len > chunk and -(-prompt_len // chunk) * chunk <= self.max_len:
            return
        self.bucket_for(prompt_len)

    def validate_request(
        self,
        prompt_ids: Sequence[int],
        max_new_tokens: int,
        *,
        temperature: Optional[float] = None,
        top_k: int = 0,
        top_p: float = 1.0,
    ) -> Tuple[np.ndarray, int, float, int, float]:
        """Normalize one request, raising ``ValueError`` for anything the engine
        cannot serve (empty/oversized prompt, bad budget or sampling controls).
        Returns ``(prompt, budget, temperature, top_k, top_p)``."""
        prompt = np.asarray(prompt_ids, dtype=np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if prompt.size >= self.max_len:
            raise ValueError(f"prompt length {prompt.size} >= max_len ({self.max_len})")
        from unionml_tpu.ops.sampling import validate_sampling

        temperature, top_k, top_p = validate_sampling(temperature, top_k, top_p)
        temperature = self.temperature if temperature is None else temperature
        try:
            self.check_prefillable(int(prompt.size))  # raises for prompts no prefill path takes
        except ValueError:
            # a cached prefix can stand in for the missing bucket: only the
            # uncovered suffix runs prefill, so a preempted transcript longer
            # than the largest bucket (its blocks pinned) still re-admits
            if not self._prefix_coverable(prompt):
                raise
        if self.paged:
            demand = self.block_demand(prompt.size, max_new_tokens)
            if demand > self._allocator.num_blocks:
                # PERMANENT: no amount of retirement frees enough blocks, so
                # reject now (ValueError) instead of the retryable
                # pool_exhausted failure transient contention raises
                raise ValueError(
                    f"request needs {demand} KV blocks but the pool has only "
                    f"{self._allocator.num_blocks}; raise pool_blocks or lower "
                    "max_new_tokens"
                )
        return prompt, int(max_new_tokens), float(temperature), int(top_k), float(top_p)

    def _prefix_coverable(self, prompt: np.ndarray) -> bool:
        """True when the cached prefix of ``prompt`` leaves a suffix that fits
        the bucket ladder and the slot's cache rows — the admission path a
        preempted transcript resumes through. A non-acquiring probe: the
        actual match happens at admission (pinned resume blocks cannot be
        evicted in between)."""
        if self.prefix_cache is None:
            return False
        if self.prefill_chunk is not None and int(prompt.size) < self.max_len:
            return True  # the chunked path handles any in-capacity suffix
        block = self._prefix_block_size
        covered = self.prefix_cache.probe(prompt, (int(prompt.size) - 1) // block) * block
        if covered <= 0:
            return False
        try:
            return covered + self.bucket_for(int(prompt.size) - covered) <= self.max_len
        except ValueError:
            return False

    # ------------------------------------------------------------- paged blocks

    def block_demand(self, prompt_len: int, budget: int) -> int:
        """Pool blocks one request needs for its WHOLE lifetime: prompt plus
        budget, capped by cache capacity (generation force-finishes at
        ``max_len - 1``). Zero on dense engines (no block accounting) — and a
        prefix-cache hit at admission can shrink the private share below this,
        so it is the CONSERVATIVE demand the batcher gates on."""
        if not self.paged:
            return 0
        return block_demand(
            prompt_len, budget, max_len=self.max_len, block_size=self._prefix_block_size
        )

    def available_blocks(self) -> Optional[int]:
        """Blocks an admission could allocate right now — the free list plus
        every evictable cached chain; ``None`` on dense engines (unbounded).
        The batcher gates admission and block-pressure preemption on this."""
        if not self.paged:
            return None
        return self._allocator.available_blocks()

    def pool_signal(self) -> Optional[Dict[str, Any]]:
        """Counter-derived block-pool occupancy for the scheduler's
        :meth:`~unionml_tpu.serving.scheduler.SLOScheduler.load_signal`
        (fleet routing + autoscaling): ``None`` on dense engines, else
        ``num_blocks``, the free/live/cached/pinned fractions,
        ``available_blocks`` (free plus cached-minus-pinned — an upper
        bound on what eviction could reclaim), and ``pressure`` (1 minus
        the available fraction). Plain counter reads only — the EXACT
        evictable-chain walk (:meth:`available_blocks`) stays on the
        worker-thread admission path, because it traverses the radix tree
        this signal must not race with."""
        if not self.paged:
            return None
        stats = self._allocator.stats()
        total = max(1, int(stats["num_blocks"]))
        free = int(stats["free_blocks"])
        live = int(stats["slot_blocks"])
        cached = int(stats["cached_blocks"])
        pinned = int(stats["pinned_blocks"])
        available = max(0, min(total, free + cached - pinned))
        return {
            "num_blocks": total,
            "free_frac": round(free / total, 4),
            "live_frac": round(live / total, 4),
            "cached_frac": round(cached / total, 4),
            "pinned_frac": round(pinned / total, 4),
            "available_blocks": available,
            "pressure": round(1.0 - available / total, 4),
        }

    # transfers: kv-block
    def _alloc_slot_blocks(self, slot: int, start: int, need: int, gauges: bool = True) -> List[int]:
        """Acquire ``need`` private pool blocks for ``slot``'s table columns
        ``[start, start+need)``, flushing the in-flight burst once on shortfall
        (its unreplayed retirements may be sitting on frees). Still short →
        the structured pool-exhaustion failure: ``retryable``, because blocks
        free as live requests retire. The grant is recorded in
        ``_slot_block_map`` immediately, so every unwind path (cancel, the
        admission orphan sweep) sees the ownership. A caller that allocates
        for several slots passes ``gauges=False`` and refreshes the pool
        gauges itself, once, after the last."""
        if need <= 0:
            self._slot_block_map.setdefault(slot, {})
            return []
        ids = self._allocator.alloc_blocks(need)
        if ids is None:
            if self._inflight is not None:
                self._flush_inflight()
                ids = self._allocator.alloc_blocks(need)
            if ids is None:
                raise EngineFailure(
                    f"KV block pool exhausted: need {need} block(s), "
                    f"{self._allocator.available_blocks()} reclaimable of "
                    f"{self._allocator.num_blocks}",
                    reason="pool_exhausted", retryable=True,
                )
        self._slot_block_map[slot] = {start + i: b for i, b in enumerate(ids)}
        if self._telemetry is not None:
            self._telemetry.blocks_per_request.observe(float(need))
            self._note_span(slot, "block_alloc", blocks=need, shared=start)
            if gauges:
                self._note_pool_gauges()
        return ids

    # owns: kv-block
    def _free_slot_blocks(self, slot: int) -> None:
        """Return ``slot``'s remaining private blocks to the allocator
        (retire / cancel / quarantine / preempt leftovers — blocks the radix
        index adopted already left the map). Safe mid-pipeline: see the
        ordering note on ``_slot_block_map``."""
        ids = self._slot_block_map.pop(slot, None)
        if ids:
            self._allocator.free_blocks(list(ids.values()))
            if self._telemetry is not None:
                self._note_pool_gauges()

    def _note_pool_gauges(self) -> None:
        """Refresh the pool-occupancy gauges (host counters only — no device
        work; callers gate on ``self._telemetry is not None``)."""
        stats = self._allocator.stats()
        self._telemetry.pool_free_blocks.set(float(stats["free_blocks"]))
        self._telemetry.pool_live_blocks.set(float(stats["slot_blocks"]))
        self._telemetry.pool_cached_blocks.set(float(stats["cached_blocks"]))
        self._telemetry.pool_pinned_blocks.set(float(stats["pinned_blocks"]))
        kv = self.kv_pool_stats()
        if kv:  # {} on dense engines / before the pool exists
            self._telemetry.pool_kv_bytes.set(float(kv["kv_pool_bytes"]), kv["kv_dtype"])
            self._telemetry.pool_kv_bytes_dense_equiv.set(float(kv["kv_pool_bytes_dense_equiv"]))
            if kv.get("impl"):
                self._telemetry.paged_attn_impl.set(1.0, kv["impl"])

    def kv_pool_stats(self) -> Dict[str, Any]:
        """Byte accounting of the resident KV pool layout (shapes only — no
        device sync): ``kv_dtype`` (what crosses HBM per decode gather),
        ``kv_pool_bytes`` (as stored, scale arrays included) and
        ``kv_pool_bytes_dense_equiv`` (the same positions priced at the full
        compute dtype — what capacity dashboards compare against). Empty on
        dense engines (their per-slot caches are not pool-accounted)."""
        if not self.paged or self._pool is None:
            return {}
        stored, full = self._layout.pool_bytes(self._pool)
        return {
            "kv_dtype": self.kv_quantize or str(jnp.dtype(self._config.dtype).name),
            "kv_pool_bytes": stored,
            "kv_pool_bytes_dense_equiv": full,
            # which decode-attention backend this replica's traced programs
            # run ("pallas" = fused paged kernel, "xla" = gather + attend)
            "impl": self.paged_attn_impl,
        }

    def _write_slot_row(self, slot: int, block_ids: Sequence[int]) -> None:
        """Upload one slot's block-table row: shared spliced prefix ids first,
        then private ids; every unmapped tail column points at scratch, so the
        row's masked writes always land somewhere harmless. One EXPLICIT
        ``device_put`` plus a point-update dispatch (same admission-path
        transfer discipline as ``_slot_device_update``); the in-flight step
        keeps the OLD tables array, so this never disturbs a running burst."""
        row = np.full((self._table_width,), self._scratch_block, dtype=np.int32)
        row[: len(block_ids)] = block_ids
        try:
            self._tables = self._write_row_fn(
                self._tables, *jax.device_put((np.int32(slot), row))
            )
        except Exception:
            # the row write donates the tables: a failure here consumed them
            self._device_poisoned = True
            raise

    def _activate(self, slot: int, length: int, budget: int, temp: float, top_k: int, top_p: float) -> None:
        self._mark_active(slot, length, budget, temp, top_k, top_p)
        self._slot_device_update(slot, True, budget, temp, top_k, top_p)

    def _mark_active(self, slot: int, length: int, budget: int, temp: float, top_k: int, top_p: float) -> None:
        """The host half of an activation: the numpy mirrors and the
        admission bookkeeping (a paged wave sets the device mirrors inside its
        own program)."""
        self._active[slot] = True
        self._reserved[slot] = False
        self._lens_host[slot] = length
        self._remaining[slot] = budget
        self._slot_temp[slot] = temp
        self._slot_top_k[slot] = top_k
        self._slot_top_p[slot] = top_p
        self.requests_admitted += 1
        if self._admitting is not None:
            self._admitting.append(slot)

    def _slot_device_update(
        self, slot: int, is_active: bool, budget: int, temp: float, top_k: int, top_p: float
    ) -> None:
        """Mirror one slot's lifecycle + sampling controls onto the device with
        a single point-update dispatch. Admission and cancel go through here —
        never a full host upload, which would roll back OTHER slots' in-flight
        device-side retirements — so step() pays zero per-tick host→device
        transfers for any of these vectors. The scalar uploads are one EXPLICIT
        ``device_put`` (a python scalar at the jit boundary is an implicit
        transfer, which the transfer-guard admission regression disallows)."""
        scalars = jax.device_put((
            np.int32(slot), np.bool_(is_active),
            np.int32(min(int(budget), np.iinfo(np.int32).max)),
            np.float32(temp), np.int32(top_k), np.float32(top_p),
        ))
        try:
            (
                self._active_dev,
                self._remaining_dev,
                self._temp_dev,
                self._top_k_dev,
                self._top_p_dev,
            ) = self._slot_update_fn(
                self._active_dev, self._remaining_dev,
                self._temp_dev, self._top_k_dev, self._top_p_dev,
                *scalars,
            )
        except Exception:
            # the point-update donates every slot mirror: a failure here left
            # them consumed, which the public entry points escalate
            self._device_poisoned = True
            raise

    def add_request(
        self,
        prompt_ids: Sequence[int],
        max_new_tokens: int,
        *,
        temperature: Optional[float] = None,
        top_k: int = 0,
        top_p: float = 1.0,
    ) -> int:
        """Prefill ``prompt_ids`` into a free slot; returns the slot index.

        ``temperature`` (``None`` = the engine default), ``top_k`` (``0`` = off)
        and ``top_p`` (``1.0`` = off) set THIS request's sampling controls; slots
        with heterogeneous settings share every decode step (one program, per-row
        controls — :mod:`unionml_tpu.ops.sampling`).

        Raises ``RuntimeError`` when no slot is free (callers should gate on
        ``free_slots``) and ``ValueError`` for empty/oversized prompts. The
        effective budget is capped by cache capacity: generation force-finishes
        when the slot's length reaches ``max_len - 1``.

        The single-request form of :meth:`admit_many`.
        """
        return self.admit_many(
            [(prompt_ids, max_new_tokens, dict(temperature=temperature, top_k=top_k, top_p=top_p))]
        )[0]

    def admit_many(self, requests: Sequence[Tuple]) -> List[int]:
        """Admit several requests at once with BATCHED bucket prefills.

        ``requests`` is a sequence of ``(prompt_ids, max_new_tokens)`` or
        ``(prompt_ids, max_new_tokens, sampling_dict)``. Prompts sharing a
        prefill bucket run through ONE (rows, bucket) prefill dispatch, up to
        ``prefill_batch`` rows each — N queued prompts admit in
        ⌈N/prefill_batch⌉ dispatches per bucket instead of N. Prompts longer
        than ``prefill_chunk`` (when configured) admit as chunked prefills
        advanced one chunk per :meth:`step` instead.

        All requests validate BEFORE any device work (one bad request rejects
        the call with nothing scheduled); ``RuntimeError`` when fewer slots are
        free than requests. Returns the assigned slot per request, in order.

        With the prefix cache enabled, admission is TWO-PASS: a request whose
        prefix a same-call sibling is about to index (detected on host, by
        token-block comparison) defers to a second pass and restores that KV
        instead of recomputing it — a cold burst of N same-prefix prompts pays
        ONE full prefill plus N-1 suffixes, not N full prefills.

        Admission is ATOMIC against non-poisoning failures: when an admission
        dies without consuming shared engine state, every slot this call
        already admitted is cancelled (and every block it was granted freed)
        before the exception re-raises, so the caller can attribute the
        failure per-request by re-admitting one at a time (the batcher does
        exactly this). A failure that consumed donated engine state escalates
        to a full engine failure instead — salvage captured, device state
        rebuilt in place (see :meth:`rebuild`). On the paged engine a bucketed
        wave is ONE program that donates the pool, the tables, the lengths,
        the logits and the slot mirrors, so what unwinds cleanly there is what
        fails before that dispatch: validation, slot and block shortage
        (``pool_exhausted``), an injected prefill fault, the wave's upload.
        Any exception out of the dispatch itself is a full engine failure.
        The dense engine's prefill dispatch donates nothing and still unwinds
        cleanly; its insert and point-updates escalate.
        """
        self._ensure_usable()
        if self._faults is not None:
            self._faults.begin_admit()
        failures_before = self.failure_count
        self._admitting = []
        try:
            return self._admit_many_inner(requests)
        except Exception:
            if self.failure_count == failures_before:
                if self._device_poisoned:
                    # a donating dispatch died mid-admission: the shared
                    # engine state is consumed, so this is a full failure
                    self._on_failure()
                else:
                    # clean unwind: the engine (and every other request) is
                    # intact — only this call's own admissions roll back
                    for slot in list(self._admitting):
                        self.cancel(slot)
                    if self.paged:
                        # blocks granted to slots that never reached _activate
                        # (a sibling's dispatch died mid-batch): sweep them
                        for slot in list(self._slot_block_map):
                            if not (self._active[slot] or self._reserved[slot]):
                                self._free_slot_blocks(slot)
            raise
        finally:
            self._admitting = None
            if self._faults is not None:
                self._faults.end_admit()

    def _admit_many_inner(self, requests: Sequence[Tuple]) -> List[int]:
        normalized = []
        for req in requests:
            prompt_ids, budget = req[0], req[1]
            sampling = dict(req[2]) if len(req) > 2 and req[2] else {}
            normalized.append(self.validate_request(prompt_ids, budget, **sampling))
        free = self.free_slots
        if len(normalized) > len(free) and self._inflight is not None:
            # the in-flight pipelined step may hold retirements the host has not
            # replayed yet: fetch it before refusing, so admission is exactly as
            # responsive as an unpipelined engine (the events reach the caller
            # through the next step())
            self._flush_inflight()
            free = self.free_slots
        if len(normalized) > len(free):
            raise RuntimeError("no free decode slots")
        slots = [free[i] for i in range(len(normalized))]

        groups: Dict[int, List[int]] = {}
        deferred: List[int] = []
        sibling_prefixes: set = set()
        for i, norm in enumerate(normalized):
            prompt = norm[0]
            if self.prefix_cache is not None:
                if self._defer_for_sibling(prompt, sibling_prefixes):
                    deferred.append(i)
                    continue
                self._note_prefixes(prompt, sibling_prefixes)
            self._admit_one(slots[i], norm, groups)
        self._flush_groups(groups, normalized, slots)
        if deferred:
            # the siblings' blocks are indexed now: deferred requests re-match
            # and admit as hits (or fall back cleanly if the pool filled up)
            groups = {}
            for i in deferred:
                self._admit_one(slots[i], normalized[i], groups)
            self._flush_groups(groups, normalized, slots)
        return slots

    def _admit_one(self, slot: int, norm: Tuple, groups: Dict[int, List[int]]) -> None:
        """Route one validated request: chunked prefill, one-shot prefix-cache
        hit, or the batched bucket path (queued in ``groups`` for
        :meth:`_flush_groups`). Prefix matching happens here so the chunked and
        one-shot paths both see the restored-prefix length."""
        prompt, budget, temp, top_k, top_p = norm
        path, matched = self._match_prefix(prompt)
        if self._start_chunked(slot, prompt, budget, temp, top_k, top_p, path, matched):
            return
        if matched and self._admit_with_prefix(
            slot, prompt, budget, temp, top_k, top_p, path, matched
        ):
            return
        groups.setdefault(self.bucket_for(prompt.size), []).append(slot)

    def _flush_groups(
        self, groups: Dict[int, List[int]], normalized: Sequence[Tuple], slots: Sequence[int]
    ) -> None:
        """Run the batched bucket prefills: per bucket, waves of up to
        ``prefill_batch`` rows. A paged wave is one upload and one program
        (:meth:`_dispatch_wave`); the dense engine's is a prefill dispatch,
        one scatter into the slot cache rows and a point-update a row."""
        slot_to_norm = {slot: norm for slot, norm in zip(slots, normalized)}
        for bucket, idxs in groups.items():
            for start in range(0, len(idxs), self.prefill_batch):
                chunk = idxs[start : start + self.prefill_batch]
                rows = len(chunk)
                self.timeline.enter("prefill", rows=rows, bucket=int(bucket))
                if self.paged:
                    self._dispatch_wave(int(bucket), [(slot, *slot_to_norm[slot]) for slot in chunk])
                    activate = self._mark_active  # the wave's program set the device mirrors
                else:
                    padded = np.zeros((rows, bucket), dtype=np.int32)
                    lengths = np.zeros((rows,), dtype=np.int32)
                    for r, slot in enumerate(chunk):
                        prompt = slot_to_norm[slot][0]
                        padded[r, : prompt.size] = prompt
                        lengths[r] = prompt.size
                    if self._faults is not None:
                        self._faults.check_prefill()
                    local_cache, local_logits = self._prefill_fn(
                        self._variables, jnp.asarray(padded), jnp.asarray(lengths)
                    )
                    self._insert_into_slots(
                        local_cache, local_logits,
                        jnp.asarray(chunk, dtype=jnp.int32),
                        jnp.asarray(lengths),
                    )
                    activate = self._activate
                self.prefill_dispatches += 1
                for slot in chunk:
                    prompt, budget, temp, top_k, top_p = slot_to_norm[slot]
                    activate(slot, int(prompt.size), budget, temp, top_k, top_p)
                    self.prefill_tokens_computed += int(prompt.size)
                    self._note_state_reset(int(prompt.size))
                    self._index_prompt(slot, prompt)
                    if self._telemetry is not None:
                        self._telemetry.prefill_tokens_total.inc(float(prompt.size))
                        self._note_span(
                            slot, "prefill",
                            tokens=int(prompt.size), bucket=int(bucket), batch_rows=rows,
                        )

    def _dispatch_wave(self, bucket: int, wave_rows: Sequence[Tuple]) -> None:
        """One bucketed admission wave of a paged engine, on the device: the
        rows ``(slot, prompt, budget, temperature, top_k, top_p)`` get their
        pool blocks (each slot's table row maps exactly its lifetime demand;
        bucket padding past it lands on the row's scratch tail), everything
        the program reads goes up in ONE explicit ``device_put`` (an int32
        array, laid out as :data:`_WAVE_SCALARS` says) and ONE donating
        dispatch does the rest (``_prefill_wave``). The in-flight step keeps
        the old tables and mirrors, so a running burst is not disturbed. An
        injected prefill fault fires before the upload, with nothing donated:
        a clean unwind. A failure of the dispatch itself has CONSUMED the
        pool, the tables, lengths, logits and slot mirrors, so it marks the
        device state poisoned and the public entry point escalates."""
        scalars_at = bucket + _WAVE_SCALARS
        wave = np.zeros((len(wave_rows), scalars_at + self._table_width), dtype=np.int32)
        wave[:, scalars_at:] = self._scratch_block
        floats = wave[:, scalars_at - 2 : scalars_at].view(np.float32)  # the same memory, as floats
        for r, (slot, prompt, budget, temp, top_k, top_p) in enumerate(wave_rows):
            private = self._alloc_slot_blocks(
                slot, 0, self.block_demand(prompt.size, budget), gauges=False
            )
            wave[r, : prompt.size] = prompt
            wave[r, bucket : scalars_at - 2] = (
                slot, prompt.size, min(int(budget), np.iinfo(np.int32).max), top_k
            )
            floats[r] = (temp, top_p)
            wave[r, scalars_at : scalars_at + len(private)] = private
        if self._telemetry is not None:
            self._note_pool_gauges()
        if self._faults is not None:
            self._faults.check_prefill()
        self.wave_device_calls += 1
        wave = jax.device_put(wave, self._replicated)
        self.wave_device_calls += 1
        try:
            (
                self._pool, self._tables, self._lens, self._last_logits,
                self._active_dev, self._remaining_dev,
                self._temp_dev, self._top_k_dev, self._top_p_dev,
            ) = self._prefill_wave_fn(
                self._variables, self._pool, self._tables, self._lens, self._last_logits,
                self._active_dev, self._remaining_dev,
                self._temp_dev, self._top_k_dev, self._top_p_dev, wave,
            )
        except Exception:
            self._device_poisoned = True
            raise

    def _note_state_reset(self, tokens: int, first: bool = True) -> None:
        """Count one prefill call of ``tokens`` real tokens for a layout with
        per-slot state: the admission's reset (its ``first`` call) and the
        positions that the tail layers did not see (all but the one read)."""
        if self._layout.slot_state and first:
            self.state_resets += 1
        if self._layout.tail_layers:
            self.cross_rows_skipped += tokens - 1

    def _defer_for_sibling(self, prompt: np.ndarray, sibling_prefixes: set) -> bool:
        """True when an earlier request in THIS admit_many call is about to
        index a longer block-prefix of ``prompt`` than the tree matches today —
        deferring lets this request restore that KV instead of recomputing it."""
        block = self._prefix_block_size
        max_blocks = (int(prompt.size) - 1) // block
        for k in range(max_blocks, 0, -1):
            if tuple(int(t) for t in prompt[: k * block]) in sibling_prefixes:
                return k > self.prefix_cache.probe(prompt, max_blocks)
        return False

    def _note_prefixes(self, prompt: np.ndarray, sibling_prefixes: set) -> None:
        """Record every block-prefix this request will index once it prefills
        (its full blocks), for :meth:`_defer_for_sibling` checks that follow."""
        block = self._prefix_block_size
        for k in range(1, int(prompt.size) // block + 1):
            sibling_prefixes.add(tuple(int(t) for t in prompt[: k * block]))

    # -------------------------------------------------------------- prefix cache

    def _match_prefix(self, prompt: np.ndarray) -> Tuple[List[Any], int]:
        """Longest cached full-block prefix of ``prompt``; ``([], 0)`` when the
        cache is disabled or nothing matches. Matching is capped one token short
        of the prompt: at least one real token must run prefill to produce the
        ``last_logits`` that seed decoding. The returned node path is
        reference-held until the slot retires (or admission declines the hit).
        """
        if self.prefix_cache is None:
            return [], 0
        max_blocks = (int(prompt.size) - 1) // self._prefix_block_size
        if max_blocks <= 0:
            return [], 0
        path = self.prefix_cache.match(prompt, max_blocks)
        return path, len(path) * self._prefix_block_size

    def _admit_with_prefix(
        self, slot: int, prompt: np.ndarray, budget: int,
        temp: float, top_k: int, top_p: float, path: List[Any], matched: int,
    ) -> bool:
        """One-shot admission of a prefix-cache hit: restore the matched blocks
        into a batch-1 local cache (shard-local gather), prefill ONLY the
        uncovered suffix over it (bucket-padded, the chunk program), insert into
        the slot. The match shrinks block-by-block if the suffix bucket would
        overflow the slot's cache rows; returns False (path fully released) when
        nothing survives, and the caller falls back to the batched bucket path.
        """
        block = self._prefix_block_size
        while matched:
            try:
                if matched + self.bucket_for(prompt.size - matched) <= self.max_len:
                    break
            except ValueError:
                # the suffix outgrew the bucket ladder while shrinking: this
                # prompt is only admissible through its cached prefix, so the
                # hit path cannot proceed — release and fall back (the caller
                # raises a clean oversized-prompt error)
                self.prefix_cache.release(path)
                path.clear()
                return False
            self.prefix_cache.release([path.pop()])
            matched -= block
        if not matched:
            return False
        suffix_len = int(prompt.size) - matched
        bucket = self.bucket_for(suffix_len)
        self.timeline.enter("prefill", rows=1, bucket=int(bucket))
        ids = np.zeros((1, bucket), dtype=np.int32)
        ids[0, :suffix_len] = prompt[matched:]
        if self.paged:
            # COPY-FREE restore: the matched blocks are already pool-resident,
            # so the hit just splices their ids into the slot's table row and
            # runs the suffix prefill over the gathered row — no copy-out
            # dispatch at all (the restore counter still ticks: it now counts
            # logical restores, and stays comparable with the dense engine)
            try:
                private = self._alloc_slot_blocks(
                    slot, len(path), self.block_demand(prompt.size, budget) - len(path)
                )
                self._write_slot_row(slot, [node.block_id for node in path] + private)
                self.prefix_restore_dispatches += 1
                if self._faults is not None:
                    self._faults.check_prefill()
                last = self._run_paged_chunk(ids, slot, matched, suffix_len - 1)
                self.prefill_dispatches += 1
                self.prefill_tokens_computed += suffix_len
                self._seal_slot(slot, int(prompt.size), last)
            except Exception:
                # release the matched-path references AND the private grant
                # (a poisoning failure clears the allocator wholesale anyway;
                # a clean one — pool_exhausted, injected prefill — must not
                # strand either resource)
                self.prefix_cache.release(path)
                path.clear()
                self._free_slot_blocks(slot)
                raise
        else:
            pad_len = matched + bucket  # exact: the suffix write never clamps
            # hit-admission uploads are EXPLICIT device_puts: this is one of the
            # two hot entry points the transfer-guard regression drives under
            # disallow-implicit, so every host array states its transfer
            block_ids = jax.device_put(
                np.asarray([node.block_id for node in path], dtype=np.int32)
            )
            local_cache = self._restore_fn(self._pool, block_ids, pad_len)
            self.prefix_restore_dispatches += 1
            try:
                if self._faults is not None:
                    self._faults.check_prefill()
                last, local_cache = self._chunk_fn(
                    self._variables, jax.device_put(ids), local_cache,
                    *jax.device_put((np.int32(matched), np.int32(suffix_len - 1))),
                )
                self.prefill_dispatches += 1
                self.prefill_tokens_computed += suffix_len
                self._insert_into_slots(
                    local_cache, last,
                    jax.device_put(np.asarray([slot], dtype=np.int32)),
                    jax.device_put(np.asarray([prompt.size], dtype=np.int32)),
                )
            except Exception:
                # whatever died, this request's matched-path references must not
                # leak with it (the blocks stay indexed for future hits)
                self.prefix_cache.release(path)
                path.clear()
                raise
        self.prefix_cache.record_hit(matched)
        self._activate(slot, int(prompt.size), budget, temp, top_k, top_p)
        self._slot_path[slot] = path
        self._index_prompt(slot, prompt)
        if self._telemetry is not None:
            self._telemetry.prefill_tokens_total.inc(float(suffix_len))
            self._note_span(slot, "prefix_hit", matched_tokens=matched, blocks=len(path))
            self._note_span(slot, "prefill", tokens=suffix_len, restored=matched)
        return True

    def _run_paged_chunk(self, ids: np.ndarray, slot: int, position: int, pick: int = 0) -> Any:
        """Dispatch one batch-1 prefill chunk straight into ``slot``'s pool
        blocks (``_paged_chunk_fn``) and return the (1, vocab) logits of its
        token ``pick``. The pool is DONATED: a dispatch failure consumed the
        only KV storage, so it poisons the device state — unlike the dense
        chunked path, a paged chunk death always escalates."""
        try:
            logits, self._pool = self._paged_chunk_fn(
                self._variables, jax.device_put(ids), self._pool, self._tables,
                *jax.device_put((np.int32(slot), np.int32(position), np.int32(pick))),
            )
        except Exception:
            self._device_poisoned = True
            raise
        return logits

    def _seal_slot(self, slot: int, length: int, last: Any) -> None:
        """Point-update one table-resident prefill's length + sampling logits
        (``_finish_slot_fn`` donates both vectors — failure poisons them)."""
        try:
            self._lens, self._last_logits = self._finish_slot_fn(
                self._lens, self._last_logits,
                *jax.device_put((np.int32(slot), np.int32(length))), last,
            )
        except Exception:
            self._device_poisoned = True
            raise

    def _index_prompt(self, slot: int, prompt: np.ndarray) -> None:
        """Start the slot's token transcript and (cache on) index the prompt's
        KV into the pool. Runs AFTER :meth:`_activate`, on every admission path.

        The transcript serves generated-KV capture at retirement
        (``prefix_cache_generated``), preempt-to-prefix-cache checkpointing,
        AND failure salvage — the last works without the cache, so the
        transcript is kept unconditionally (host ints: cost is trivial)."""
        self._slot_tokens[slot] = [int(t) for t in prompt]
        if self.prefix_cache is None:
            return
        self._extend_index(slot, prompt)

    def _extend_index(self, slot: int, tokens: np.ndarray) -> None:
        """Extend the slot's held radix path over ``tokens``' full blocks and
        device-copy KV for the NEW blocks out of the slot's cache rows.

        Caching failures never kill the request: an exhausted pool (every
        block referenced — or injected) simply indexes nothing new, and a
        failed block save (which donates, i.e. poisons, only the POOL) rebuilds
        the pool in place and forgets every cached prefix — the slot cache,
        and therefore the request, are untouched either way."""
        path = self._slot_path.pop(slot, [])
        if self._faults is not None and self._faults.pool_exhausted():
            # injected exhaustion: behave exactly like extend() against a
            # fully-referenced pool — keep what is held, index nothing new
            self._faults.note_observed("pool_exhausted")
            if path:
                self._slot_path[slot] = path
            return
        if self.paged:
            # ADOPTION, not a copy: the slot's own blocks already hold exactly
            # the KV the tree wants, so indexing moves ownership slot → tree
            # for each full block the tree lacks — zero device work. Where a
            # sibling indexed the same block first, the existing node wins and
            # the slot keeps (and later frees) its identical duplicate.
            # ownership moves kv-block slot → radix tree via block_map pops
            full, adopted = self.prefix_cache.adopt(
                path, tokens, int(tokens.size) // self._prefix_block_size,
                self._slot_block_map.setdefault(slot, {}),
            )
            if adopted:
                # one logical save per adoption event: keeps the counter
                # comparable with the dense engine's per-retirement save
                self.prefix_save_dispatches += 1
                if self._telemetry is not None:
                    self._note_pool_gauges()
            if full:
                self._slot_path[slot] = full
            return
        # graftlint: disable=resource-leak -- the pool-rebuild return path drops 'full' deliberately: _rebuild_pool() forgets every cached prefix, so the refs die with the rebuilt cache
        full, new = self.prefix_cache.extend(
            path, tokens, int(tokens.size) // self._prefix_block_size
        )
        if new:
            start = len(full) - len(new)  # new nodes are always the path's tail
            # explicit uploads: block saves run at retirement, INSIDE the
            # steady-state step path the transfer guard disallows implicits on
            dst = jax.device_put(np.asarray([node.block_id for node in new], dtype=np.int32))
            try:
                self._pool = self._save_fn(
                    self._pool, self._cache, jax.device_put(np.int32(slot)),
                    jax.device_put(np.int32(start)), dst, self._prefix_block_size,
                )
            except Exception as exc:
                logger.warning(
                    "prefix-cache block save failed (%s); rebuilding the pool in place", exc
                )
                self._rebuild_pool()
                return
            self.prefix_save_dispatches += 1
        if full:
            self._slot_path[slot] = full

    def _rebuild_pool(self) -> None:
        """Reallocate the (poisoned or reset) KV block pool and forget every
        cached prefix. Held node paths — other slots', pinned checkpoints' —
        now reference orphaned nodes; their later release/unpin calls mutate
        those orphans harmlessly, and re-admissions simply re-index."""
        self.prefix_cache.clear()
        self._slot_path.clear()
        self._pool = self._layout.init_block_pool(
            self.prefix_cache.num_blocks, self._prefix_block_size
        )
        if self._mesh is not None:
            self._pool = jax.device_put(self._pool, self._cache_sharding)

    def _capture_generated(self, slot: int) -> None:
        """At retirement (``prefix_cache_generated``): index the slot's FULL
        token transcript — prompt plus every decoded token, eos included — so a
        multi-turn follow-up hits the whole previous turn. Cache columns map
        1:1 to transcript positions; the valid count is the slot's length."""
        tokens = self._slot_tokens.get(slot)
        if not tokens:
            return
        valid = int(self._lens_host[slot])
        self._extend_index(slot, np.asarray(tokens[:valid], dtype=np.int32))

    def _release_prefix(self, slot: int) -> None:
        """Drop the slot's references into the radix tree (retirement/cancel)."""
        path = self._slot_path.pop(slot, None)
        if path and self.prefix_cache is not None:
            self.prefix_cache.release(path)
        self._slot_tokens.pop(slot, None)

    # ------------------------------------------------------------- chunked prefill

    def _start_chunked(self, slot: int, prompt: np.ndarray, budget: int,
                       temp: float, top_k: int, top_p: float,
                       path: Sequence[Any] = (), matched: int = 0) -> bool:
        """Reserve ``slot`` for a chunked prefill when the prompt qualifies.

        Qualifies when ``prefill_chunk`` is configured, the UNCOVERED part of
        the prompt (``matched`` tokens restore from the prefix cache) is longer
        than one chunk, and the padded length still fits the slot's cache rows
        (otherwise the one-shot hit / bucketed batch paths handle it). With a
        hit, the local cache starts as the restored prefix and chunking resumes
        at ``consumed = matched``; the pad length anchors at ``matched`` so the
        final chunk's cache write never clamps."""
        chunk = self.prefill_chunk
        if chunk is None or prompt.size - matched <= chunk:
            return False
        padded_len = matched + -(-(prompt.size - matched) // chunk) * chunk
        if padded_len > self.max_len:
            return False
        if self.paged:
            # no local workspace at all: allocate the slot's lifetime blocks,
            # splice any matched prefix straight into the row, and let every
            # chunk write through the table (``_run_paged_chunk``)
            try:
                private = self._alloc_slot_blocks(
                    slot, len(path), self.block_demand(prompt.size, budget) - len(path)
                )
                self._write_slot_row(slot, [node.block_id for node in path] + private)
            except Exception:
                if path:
                    self.prefix_cache.release(list(path))
                self._free_slot_blocks(slot)
                raise
            local_cache = None
            if matched:
                self.prefix_restore_dispatches += 1  # copy-free splice
                self.prefix_cache.record_hit(matched)
                self._slot_path[slot] = list(path)
                if self._telemetry is not None:
                    self._note_span(slot, "prefix_hit", matched_tokens=matched, blocks=len(path))
        elif matched:
            block_ids = jnp.asarray([node.block_id for node in path], dtype=jnp.int32)
            local_cache = self._restore_fn(self._pool, block_ids, padded_len)
            self.prefix_restore_dispatches += 1
            self.prefix_cache.record_hit(matched)
            self._slot_path[slot] = list(path)
            if self._telemetry is not None:
                self._note_span(slot, "prefix_hit", matched_tokens=matched, blocks=len(path))
        else:
            local_cache = self._layout.init_cache(1, padded_len)
            if self._mesh is not None:
                local_cache = jax.device_put(local_cache, self._cache_sharding)
        self._reserved[slot] = True
        if self._admitting is not None:
            self._admitting.append(slot)
        self._partials[slot] = {
            "prompt": prompt, "consumed": matched, "cache": local_cache,
            "budget": budget, "temp": temp, "top_k": top_k, "top_p": top_p,
        }
        return True

    def _advance_partials(self) -> None:  # graftlint: off-path (admission work, not steady-state decode)
        """Run ONE chunk of every in-progress chunked prefill (called per tick,
        between decode dispatches); completed prefills insert + activate.

        A failure in a slot's OWN chunk dispatch (the chunk program donates
        only that slot's local cache) kills only that request — the partial
        is dropped and a structured ``prefill_failed`` event reaches its
        consumer — while every other slot keeps prefilling and decoding. Only
        the slot-insert dispatch (which donates the shared engine cache) can
        escalate to a whole-engine failure."""
        self.timeline.enter("prefill", rows=len(self._partials), bucket=int(self.prefill_chunk))
        for slot in list(self._partials):
            state = self._partials[slot]
            prompt, consumed = state["prompt"], state["consumed"]
            chunk = self.prefill_chunk
            take = min(chunk, prompt.size - consumed)
            # a prompt's last chunk is as wide as the smallest prefill bucket
            # that holds what is left of it, not a whole chunk of padding
            width = min((b for b in self._buckets if take <= b <= chunk), default=chunk)
            ids = np.zeros((1, width), dtype=np.int32)
            ids[0, :take] = prompt[consumed : consumed + take]
            try:
                if self._faults is not None:
                    self._faults.check_prefill()
                # the logits of the chunk's last real token: where this is the
                # prompt's final chunk they seed decoding
                if self.paged:
                    last = self._run_paged_chunk(ids, slot, int(consumed), take - 1)
                else:
                    last, state["cache"] = self._chunk_fn(
                        self._variables, jnp.asarray(ids), state["cache"],
                        jnp.asarray(consumed, dtype=jnp.int32), jnp.asarray(take - 1, dtype=jnp.int32),
                    )
            except Exception as exc:  # this slot's local dispatch: fail it alone
                if self._device_poisoned:
                    # paged chunks donate the POOL — the only KV storage — so
                    # a REAL dispatch death cannot be contained to this slot;
                    # injected prefill faults raise pre-dispatch (above) and
                    # keep the per-slot isolation contract
                    raise
                rid = self._slot_rid.get(slot)
                logger.warning(
                    "chunked prefill failed for slot %d: %s%s",
                    slot, exc, f" (request_id={rid})" if rid is not None else "",
                )
                self._fail_partial(slot)
                continue
            self.prefill_dispatches += 1
            self.prefill_tokens_computed += int(take)
            self._note_state_reset(int(take), first=consumed == 0)
            state["consumed"] = consumed + take
            if self._telemetry is not None:
                self._telemetry.prefill_tokens_total.inc(float(take))
                self._note_span(
                    slot, "prefill_chunk",
                    tokens=int(take), consumed=int(state["consumed"]), total=int(prompt.size),
                )
            if state["consumed"] < prompt.size:
                continue
            if self.paged:
                # the KV is already pool-resident behind the slot's row: only
                # the length + sampling logits need the point-update
                self._seal_slot(slot, int(prompt.size), last)
            else:
                self._insert_into_slots(
                    state["cache"], last,
                    jnp.asarray([slot], dtype=jnp.int32),
                    jnp.asarray([prompt.size], dtype=jnp.int32),
                )
            del self._partials[slot]
            self._activate(
                slot, prompt.size, state["budget"], state["temp"], state["top_k"], state["top_p"]
            )
            self._index_prompt(slot, prompt)

    def _fail_partial(self, slot: int) -> None:
        """Drop one in-progress chunked prefill whose own dispatch died: free
        the slot, release its restored-prefix references, and buffer the
        structured failure event for its consumer."""
        self._partials.pop(slot, None)
        self._reserved[slot] = False
        self._slot_queue_wait.pop(slot, None)
        self._release_prefix(slot)
        if self.paged:
            self._free_slot_blocks(slot)
        if self._telemetry is not None:
            self._drop_rid(slot)
        self._pending_events.append(
            StepEvent(slot=slot, token=-1, emit=False, finished=True, error="prefill_failed")
        )

    def _insert_into_slots(self, local_cache: Any, local_logits: Any, slots: Any, lengths: Any) -> None:
        """Run the dense engine's donating slot-insert dispatch. A failure
        here has CONSUMED the shared engine KV/lens/logits, so it marks the
        device state poisoned — the public entry point escalates to a full
        engine failure instead of pretending the batch survived."""
        try:
            self._cache, self._lens, self._last_logits = self._insert_fn(
                self._cache, self._lens, self._last_logits, local_cache, local_logits,
                slots, lengths,
            )
        except Exception:
            self._device_poisoned = True
            raise

    def reset(self) -> None:  # graftlint: off-path (error recovery, not steady-state decode)
        """Reallocate device state and clear all slots.

        Required after a failed :meth:`step`: the step donates the cache/logits
        buffers, so a deferred device error (surfacing at the token fetch, after
        the state variables were already reassigned) leaves them poisoned and out
        of sync with the host mirrors. In-flight requests are abandoned.
        """
        # the key is also a step output, so it is poisoned too; a fresh
        # reset-counted key keeps sampled streams from repeating the pre-crash run
        self._resets += 1
        self._key_steps = 0
        self.discard_salvage()
        self._failed = False
        self._device_poisoned = False
        # a dispatched-but-unfetched step is poisoned with the rest of the
        # device state: DISCARD it (never fetch), and drop its replayed events
        self._pending_events.clear()
        self._init_device_state()
        self._active[:] = False
        self._reserved[:] = False
        self._partials.clear()
        self._lens_host[:] = 0
        self._remaining[:] = 0
        self._slot_queue_wait.clear()
        self._slot_rid.clear()
        self._slot_pending_spans.clear()
        self._slot_temp[:] = self.temperature
        self._slot_top_k[:] = 0
        self._slot_top_p[:] = 1.0
        self._sync_sampling_mirrors()
        if self.paged:
            # the pool was reallocated above (_init_device_state): every block
            # returns to the free list and the radix index forgets everything,
            # held paths and pins included
            self._allocator.clear()
            self._slot_block_map.clear()
            self._slot_path.clear()
        elif self.prefix_cache is not None:
            # a full reset forgets every cached prefix too: the caller is
            # abandoning everything, held paths included
            self._rebuild_pool()
        self._slot_tokens.clear()

    # ------------------------------------------------------ failure & recovery

    @property
    def busy(self) -> bool:
        """Whether live requests should be making progress — the supervisor's
        watchdog only treats a stale heartbeat as a stall while this is True.
        Keyed on host-visible work (active slots, chunked prefills), NOT on
        ``_inflight``: a trailing dispatched-but-unfetched masked step idles
        harmlessly after the last slot retires and must not read as a stall."""
        return bool(self._active.any()) or bool(self._partials)

    @property
    def failed(self) -> bool:
        """True while an in-place rebuild has failed and not yet been retried
        successfully — the engine refuses work (the supervisor retries
        :meth:`rebuild` with backoff; unsupervised callers retry lazily)."""
        return self._failed

    def _ensure_usable(self) -> None:
        if self._failed:
            # unsupervised auto-recovery: retry the rebuild fresh-keyed (no
            # resume — whoever could have collected the salvage never did)
            self.rebuild(resume=False)

    def note_external_failure(self) -> None:
        """Escalate a poisoning failure raised from an out-of-band engine call
        (``cancel``/``preempt`` point-updates): the owner calls this from its
        catch-all so donated-state loss is never papered over. Idempotent —
        a failure already handled by the entry-point wrappers is a no-op."""
        if self._device_poisoned:
            self._on_failure()

    def _on_failure(self) -> None:  # graftlint: off-path (error recovery, not steady-state decode)
        """A device-side failure consumed donated engine state: capture every
        salvageable slot (host transcripts plus already-indexed radix paths,
        PINNED against eviction), then rebuild the device state in place with
        PRNG-stream continuity. The engine is immediately usable again; a
        supervising batcher collects :meth:`take_salvage` and re-queues the
        requests so they resume token-identically, paying only the prefill of
        whatever their pinned prefix does not cover. If the rebuild itself
        fails, the engine marks itself failed for the supervisor's
        bounded-backoff retry loop."""
        self.failure_count += 1
        self._device_poisoned = False
        # the in-flight step is poisoned with the rest: never fetch it (its
        # steps re-decode after the resume, consuming the same key stream)
        self._inflight = None
        self._inflight_skip = set()
        self._pending_events.clear()
        self._capture_salvage()
        try:
            self.rebuild(resume=True)
        except Exception:
            self._failed = True
            logger.exception("in-place engine rebuild failed; engine marked failed")

    def _capture_salvage(self) -> None:
        """Snapshot every active/reserved slot's resumable state — HOST data
        only (the device may be poisoned): the replayed transcript, the
        unspent budget, and (dense engines) whatever radix path the slot
        already held, pinned so the blocks survive the rebuild and LRU until
        the resume. PAGED engines salvage transcripts only: the pool itself
        rides the failed step's donation, so no block outlives the rebuild."""
        self.discard_salvage()  # a prior incident's uncollected records
        if self.paged:
            # return every slot-owned block NOW (host-side accounting): the
            # rebuild also clears the allocator, but if the rebuild itself
            # fails the engine must still not report leaked slot blocks
            for blk_slot in list(self._slot_block_map):
                self._free_slot_blocks(blk_slot)
        records: List[SalvagedSlot] = []
        for slot in np.flatnonzero(self._active | self._reserved):
            slot = int(slot)
            if self._reserved[slot]:
                # chunked prefill in progress: nothing delivered yet — the
                # resume is simply the original prompt at full budget
                part = self._partials.get(slot)
                tokens = [int(t) for t in part["prompt"]] if part else []
                remaining = int(part["budget"]) if part else 0
            else:
                transcript = self._slot_tokens.get(slot) or []
                valid = int(self._lens_host[slot])
                tokens = [int(t) for t in transcript[:valid]]
                remaining = int(self._remaining[slot])
            path = self._slot_path.pop(slot, [])
            if self.paged:
                # the failed step consumed the POOL — the only KV storage — so
                # no block survives the rebuild: paged salvage is TRANSCRIPT-
                # only (release the refs; the rebuild clears the tree anyway)
                # and the resume pays a full re-prefill instead of a suffix
                if path and self.prefix_cache is not None:
                    self.prefix_cache.release(path)
                path = []
            elif path and self.prefix_cache is not None and tokens and remaining > 0:
                self.prefix_cache.pin(path)
                self.prefix_cache.release(path)  # the slot's own working refs
            else:
                if path and self.prefix_cache is not None:
                    self.prefix_cache.release(path)
                path = []
            if not tokens or remaining <= 0:
                continue  # nothing to resume from
            records.append(
                SalvagedSlot(slot=slot, tokens=tokens, path=path, remaining=remaining)
            )
        self._salvage = records

    # transfers: kv-pin
    def take_salvage(self) -> List[SalvagedSlot]:
        """Collect (and clear) the salvage captured by the last failure. The
        caller owns the records' eviction pins from here on — drop each via
        :meth:`release_preempted` once its resume re-admitted or its request
        was abandoned."""
        salvage, self._salvage = self._salvage, []
        return salvage

    # owns: kv-pin
    def discard_salvage(self) -> None:
        """Unpin and drop uncollected salvage (reset/abort/unsupervised paths)."""
        for rec in self._salvage:
            if rec.path and self.prefix_cache is not None:
                self.prefix_cache.unpin(rec.path)
        self._salvage = []

    def rebuild(self, *, resume: bool = True) -> None:  # graftlint: off-path (error recovery, not steady-state decode)
        """Reallocate the engine's device state from host-retained params.

        On DENSE engines — unlike :meth:`reset` — the prefix-cache pool and
        radix index SURVIVE (block saves donate only the pool, and their
        failures rebuild it locally — see ``_extend_index``), so salvaged
        requests re-admit through the ordinary prefix-hit path and pay only a
        suffix prefill. On PAGED engines the pool IS the decode state and rode
        the failed step's donation, so the rebuild restarts the allocator and
        index empty and salvaged requests re-prefill in full.

        ``resume=True`` (supervised recovery) reconstructs the PRNG key by
        replaying the recorded number of key-consuming steps from the seeded
        base, so resumed SAMPLED streams continue token-identically to a
        fault-free run. ``resume=False`` (standalone auto-recovery; in-flight
        work abandoned) reseeds like :meth:`reset` and drops uncollected
        salvage.

        Raises when the rebuild itself fails (a real allocation error, or an
        injected ``FaultPlan.rebuild_failures``): the engine stays failed and
        the supervisor retries with bounded exponential backoff.
        """
        if self._faults is not None:
            self._faults.check_rebuild()
        if not resume:
            self._resets += 1
            self._key_steps = 0
            self.discard_salvage()
        self._pending_events.clear()
        self._active[:] = False
        self._reserved[:] = False
        self._partials.clear()
        self._lens_host[:] = 0
        self._remaining[:] = 0
        self._slot_queue_wait.clear()
        self._slot_rid.clear()
        self._slot_pending_spans.clear()
        self._slot_temp[:] = self.temperature
        self._slot_top_k[:] = 0
        self._slot_top_p[:] = 1.0
        for slot in list(self._slot_path):
            self._release_prefix(slot)  # salvage holds its own pins by now
        self._slot_tokens.clear()
        self._init_device_state()
        if self.paged:
            # the failed step consumed the pool itself; the reallocation above
            # emptied it, so the allocator and radix index restart from scratch
            # (salvage is transcript-only in paged mode for exactly this reason)
            self._allocator.clear()
            self._slot_block_map.clear()
        self._sync_sampling_mirrors()
        if resume and self._key_steps:
            # replay the consumed key advances (one split per any-active step)
            # so the stream continues exactly where the failed burst cut it
            key = self._key
            for _ in range(self._key_steps):
                key = jax.random.split(key)[0]
            if self._mesh is not None:
                key = jax.device_put(key, self._replicated)
            self._key = key
        self._device_poisoned = False
        self._failed = False
        self.rebuilds += 1

    def _apply_token(self, slot: int, token: int) -> StepEvent:
        """Advance the host mirrors for one decoded token (same rules as the
        device applies in-program — :func:`~unionml_tpu.models.gpt.advance_slot_state` —
        so host and device views re-converge at every fetch)."""
        self.tokens_decoded += 1
        self._remaining[slot] -= 1
        self._lens_host[slot] = min(self._lens_host[slot] + 1, self.max_len - 1)
        tokens = self._slot_tokens.get(slot)
        if tokens is not None:  # generated-KV capture: eos included, emit or not
            tokens.append(int(token))
        is_eos = self.eos_token_id is not None and token == self.eos_token_id
        finished = (
            is_eos
            or self._remaining[slot] <= 0
            or self._lens_host[slot] >= self.max_len - 1
        )
        # the request's first decoded token carries its queue wait, so a
        # client-side TTFT decomposes into queue vs prefill+decode time
        queue_wait_ms = self._slot_queue_wait.pop(slot, None)
        if finished:
            self._active[slot] = False
            if self.prefix_cache is not None and self.prefix_cache_generated:
                self._capture_generated(slot)  # paged: adopts blocks in place
            self._release_prefix(slot)
            if self.paged:
                # whatever the index did not adopt (partial tail, unused
                # budget) goes back to the free list right now — safe even
                # with a burst in flight (see _slot_block_map's ordering note)
                self._free_slot_blocks(slot)
            if self._telemetry is not None:
                self._drop_rid(slot)
        return StepEvent(
            slot=slot, token=token, emit=not is_eos, finished=finished,
            queue_wait_ms=queue_wait_ms,
        )

    @property
    def has_pending_events(self) -> bool:
        """Events replayed by an out-of-band pipeline flush (cancel/admission),
        awaiting delivery through the next :meth:`step` — drive loops must keep
        ticking while any are queued."""
        return bool(self._pending_events)

    def take_pending_events(self) -> List[StepEvent]:
        """Drain the events buffered by an out-of-band pipeline flush.

        Callers that keep their own slot→request mapping MUST drain these
        right after :meth:`admit_many` and attribute them under the mapping
        that existed BEFORE the call: a flush inside admission can retire a
        slot's previous occupant, and the buffered events belong to it — not
        to whichever request the freed slot was just handed to. (The
        :class:`ContinuousBatcher` does exactly this before re-keying its
        sinks.) Events left undrained are delivered by the next :meth:`step`.
        """
        events, self._pending_events = self._pending_events, []
        return events

    def pipeline_stats(self) -> Dict[str, Any]:
        """Pipeline observability for ``GET /stats``: configured depth, whether a
        step is currently in flight, the dispatch/idle counters, the active-slot
        and live-block integrals, the fan-out's hand-over counters and the loop
        thread's phase counters
        (:meth:`~unionml_tpu.profiling.PhaseTimeline.snapshot`). Everything but
        ``inflight`` only grows, so two reads difference into a window."""
        return {
            "depth": 1 if self.pipeline else 0,
            "inflight": self._inflight is not None,
            "step_dispatches": self.step_dispatches,
            "idle_dispatches": self.idle_dispatches,
            "active_slot_steps": self.active_slot_steps,
            "live_block_steps": self.live_block_steps,
            "kernel_grid_steps": self.kernel_grid_steps,
            "wave_device_calls": self.wave_device_calls,
            "sink_handoffs": self.sink_handoffs,
            "sink_deliveries": self.sink_deliveries,
            **self._residency_stats(),
            **self.model_counters,
            "phases": self.timeline.snapshot(),
        }

    def _residency_stats(self) -> Dict[str, Any]:
        """What the active slots' caches hold, for :meth:`pipeline_stats`: the
        integrals over dispatched steps ``resident_byte_steps`` (per-slot state
        and ring of every active slot, and its live blocks under the table) and
        ``live_token_steps``, whose quotient over a window is resident bytes a
        live token; the gauges ``state_bytes``, ``ring_bytes`` (all slots',
        whatever they hold) and ``kv_live_bytes`` (the active slots' live
        blocks now); and the admission counters. Empty on a dense engine."""
        if not self.paged:
            return {}
        block = self._layout.block_bytes(
            self._prefix_block_size, kv_quantize=self.kv_quantize,
            kv_quantize_skip_layers=self.kv_quantize_skip_layers,
        )
        per_slot = self._layout.slot_bytes(self._prefix_block_size)
        fixed = per_slot["state"] + per_slot["ring"]
        live_now = int(np.sum(self._lens_host[self._active] // self._prefix_block_size + 1))
        return {
            "resident_byte_steps": self.active_slot_steps * fixed + self.live_block_steps * block,
            "live_token_steps": self.live_token_steps,
            "state_bytes": per_slot["state"] * self.num_slots,
            "ring_bytes": per_slot["ring"] * self.num_slots,
            "kv_live_bytes": live_now * block,
            "state_resets": self.state_resets,
            "cross_rows_skipped": self.cross_rows_skipped,
        }

    def robustness_stats(self) -> Dict[str, Any]:
        """Engine-side robustness counters for ``GET /stats`` (the supervisor
        merges its own health/recovery counters alongside these)."""
        stats: Dict[str, Any] = {
            "engine_failures": self.failure_count,
            "engine_rebuilds": self.rebuilds,
            "quarantined_requests": self.quarantined_requests,
            "salvage_pending": len(self._salvage),
        }
        if self._faults is not None:
            stats["faults"] = self._faults.stats()
        return stats

    def note_queue_wait(self, slot: int, wait_ms: Optional[float]) -> None:
        """Record how long ``slot``'s request sat queued before admission (the
        batcher calls this right after ``admit_many``). The value rides on the
        slot's first :class:`StepEvent`; the ``unionml_queue_wait_ms``
        histogram (sum and count) is what aggregates queue waits.

        .. deprecated:: PR-11
            ``StepEvent.queue_wait_ms`` (populated only on the first token)
            is kept for compatibility; the telemetry trace's ``queue_wait``
            span is the one source of truth for TTFT decomposition.
        """
        if wait_ms is None:
            return
        self._slot_queue_wait[slot] = float(wait_ms)

    def note_request_id(self, slot: int, request_id: Optional[str]) -> None:
        """Bind ``slot``'s occupant to its trace (batcher-set at registration,
        right after :meth:`note_queue_wait`); flushes any spans the admission
        path buffered for the slot before the id was known."""
        if self._telemetry is None or request_id is None:
            return
        self._slot_rid[slot] = request_id
        for kind, at, dur_ms, attrs in self._slot_pending_spans.pop(slot, ()):
            self._telemetry.span(request_id, kind, dur_ms=dur_ms, at=at, **attrs)

    def _note_span(self, slot: int, kind: str, dur_ms: Optional[float] = None, **attrs: Any) -> None:
        """Record a slot-keyed span, buffering when the request id is not yet
        bound (admission-time prefill spans precede batcher registration).
        Callers gate on ``self._telemetry is not None`` (zero-cost-off)."""
        rid = self._slot_rid.get(slot)
        if rid is not None:
            self._telemetry.span(rid, kind, dur_ms=dur_ms, **attrs)
        else:
            self._slot_pending_spans.setdefault(slot, []).append(
                (kind, time.perf_counter(), dur_ms, attrs)
            )

    def _drop_rid(self, slot: int) -> None:
        """Forget a retired slot's trace binding (the trace itself ends at the
        batcher, which owns terminal delivery)."""
        self._slot_rid.pop(slot, None)
        self._slot_pending_spans.pop(slot, None)

    def _fetch_inflight(self) -> List[StepEvent]:
        """Fetch the dispatched-but-unfetched step (no-op when none) and replay
        its tokens into the host mirrors under the slot mapping the step was
        dispatched with."""
        if self._inflight is None:
            return []
        burst, skip = self._inflight, self._inflight_skip
        self._inflight, self._inflight_skip = None, set()
        return self._replay_burst(burst, skip)

    def _flush_inflight(self) -> None:
        """Out-of-band flush (admission short of slots or blocks, cancel,
        preempt): replay the in-flight step now and buffer its events for the
        next :meth:`step`. The loop passes through ``fetch_wait`` and ``apply``
        and returns to the phase that asked for the flush."""
        if self._inflight is None:
            return
        asked_from = self.timeline.current
        self._pending_events.extend(self._fetch_inflight())
        self.timeline.enter(asked_from)

    def _replay_burst(
        self, burst: Tuple[Any, Any, Any, int, Dict[str, Any]], skip: frozenset = frozenset()
    ) -> List[StepEvent]:
        """Block on one dispatched burst's ``(tokens, masks, bads)`` and apply them.

        ONE fused ``device_get`` for tokens, masks, and the per-step NaN
        flags; a device failure surfacing here poisons the donated buffers,
        so it fails the engine exactly like a dispatch failure. A flagged
        ``(step, slot)`` quarantines THAT slot (its sampled token is garbage
        and never delivered) while every other slot's tokens apply normally."""
        tokens, masks, bads, _, counts = burst
        t0 = self.timeline.enter("fetch_wait")
        try:
            if self._faults is not None:
                stall_ms = self._faults.take_fetch_stall_ms()
                if stall_ms is not None:
                    time.sleep(stall_ms / 1e3)  # a wedged device queue, to the watchdog's eye
                self._faults.check_fetch()
            # graftlint: disable=host-sync -- the ONE designed sync per tick: tokens+masks+nan-flags fused into a single device_get (PR-3 pipelined-decode contract)
            tokens_host, masks_host, bads_host, counts_host = jax.device_get(
                (tokens, masks, bads, counts)
            )
            tokens_host, masks_host, bads_host = map(np.asarray, (tokens_host, masks_host, bads_host))
        except Exception:
            self._on_failure()
            raise
        done = self.timeline.enter("apply")
        self.last_heartbeat = time.monotonic()
        block_ms = (done - t0) * 1e3
        self._last_fetch_done = done
        for name, per_step in counts_host.items():
            self.model_counters[name] = self.model_counters.get(name, 0) + int(np.sum(per_step))
        events: List[StepEvent] = []
        telemetry = self._telemetry
        emitted: Dict[Optional[str], int] = {}
        for i in range(tokens_host.shape[0]):
            if masks_host[i].any():
                # mirrors the in-program key gate (any(active) at step start):
                # lets a resume-rebuild replay the PRNG stream to this point
                self._key_steps += 1
            for slot in np.flatnonzero(masks_host[i]):
                slot = int(slot)
                if slot in skip:
                    # the slot was quarantined while this burst was in flight:
                    # its tokens here are garbage, and the slot may already
                    # belong to a new occupant — drop them unconditionally
                    continue
                if not self._active[slot]:
                    continue  # quarantined earlier in this burst: later steps are void
                if bads_host[i, slot]:
                    events.append(self._quarantine(slot))
                    continue
                rid = self._slot_rid.get(slot) if telemetry is not None else None
                event = self._apply_token(slot, int(tokens_host[i, slot]))
                events.append(event)
                if telemetry is not None and event.emit:
                    emitted[rid] = emitted.get(rid, 0) + 1
        if telemetry is not None and emitted:
            # per-burst decode timing piggybacks on the fetch_wait phase's two
            # stamps (t0/done/block_ms above): ZERO new host<->device syncs —
            # everything here reads the already-fetched host arrays
            telemetry.decode_fetch_ms.observe(block_ms)
            for rid, n in emitted.items():
                telemetry.decode_tokens(rid, n, at=done)
        return events

    def _quarantine(self, slot: int) -> StepEvent:
        """Terminate ONE slot whose logits went NaN/Inf: release it (without
        indexing its possibly-poisoned generated KV), point-update its device
        mirror inactive, and emit the structured failure event — siblings keep
        decoding, which is the whole point vs the old batch-wide failure."""
        self.quarantined_requests += 1
        self._active[slot] = False
        self._reserved[slot] = False
        self._remaining[slot] = 0
        self._slot_temp[slot] = self.temperature
        self._slot_top_k[slot] = 0
        self._slot_top_p[slot] = 1.0
        self._slot_queue_wait.pop(slot, None)
        self._release_prefix(slot)  # no generated-KV capture: it may be poisoned
        if self.paged:
            # NaN-poisoned block CONTENT is harmless once re-owned: the next
            # owner's prefill overwrites every position before reading it
            self._free_slot_blocks(slot)
        self._slot_device_update(slot, False, 0, self.temperature, 0, 1.0)
        if self._inflight is not None:
            # the already-dispatched next burst still decodes this slot under
            # an active mask: its replay must not credit those garbage tokens
            # to whoever occupies the slot by then
            self._inflight_skip.add(slot)
        if self._faults is not None:
            self._faults.note_observed("nan_logits")
        if self._telemetry is not None:
            self._note_span(slot, "quarantine", reason="nan_logits")
            self._telemetry.quarantines_total.inc()
        rid = self._slot_rid.get(slot)
        self._drop_rid(slot)
        logger.warning(
            "slot %d quarantined: non-finite logits%s",
            slot, f" (request_id={rid})" if rid is not None else "",
        )
        return StepEvent(slot=slot, token=-1, emit=False, finished=True, error="nan_logits")

    def _dispatch_step(self, lookahead: int) -> Tuple[Any, Any, Any, int, Dict[str, Any]]:
        """Dispatch ONE compiled decode burst; return ``(tokens, masks, bads,
        n_steps, counts)`` of the in-flight result (device arrays, not yet
        fetched): ``counts`` holds the model's step counters by name, stacked
        over the burst's steps, and is empty for a model that has none.

        The seam :meth:`step` drives and subclasses override: the speculative
        engine swaps in its round program here (returning ``n_steps`` = the
        round's burst rows) while every surrounding concern — fault paths,
        pipelining, accounting, replay — stays in :meth:`step` unchanged.
        Exceptions propagate to the caller's ``_on_failure`` path.
        """
        # the all-greedy program skips the sampling machinery; heterogeneous slots
        # share the sampling program with per-row controls. Everything the step
        # consumes — activity, budgets, sampling controls — rides as
        # device-resident mirrors (refreshed in _activate/cancel/reset), so a
        # steady-state tick performs ZERO host→device transfers (pinned by the
        # transfer-guard regression test).
        sampling = bool((self._slot_temp[self._active] > 0).any())
        fn = self._step_fns.get((lookahead, sampling))
        if fn is None:
            fn = self._step_fns[(lookahead, sampling)] = self._make_step(lookahead, sampling)
        if self._faults is not None:
            # injected dispatch failures take the SAME except path a real
            # device error takes (nothing below special-cases injection)
            self._faults.check_step_dispatch()
        if self.paged:
            # the pool rides the dispatch donated (argnums pin it); the
            # TABLES ride as a non-donated input — they only change at
            # admission, between dispatches, so the burst reads one
            # consistent map for its whole scan
            # graftlint: disable=use-after-donate -- paged _make_step donates argnums (1, 3): the pool and last_logits; self._tables at position 2 is a plain input (the dense maker's (1, 2) map does not apply to this call)
            (
                self._pool,
                self._last_logits,
                self._lens,
                self._active_dev,
                self._remaining_dev,
                self._key,
                tokens,
                masks,
                bads,
                counts,
            ) = fn(
                self._variables, self._pool, self._tables, self._last_logits,
                self._lens, self._active_dev, self._remaining_dev, self._key,
                self._temp_dev, self._top_k_dev, self._top_p_dev,
            )
        else:
            (
                self._cache,
                self._last_logits,
                self._lens,
                self._active_dev,
                self._remaining_dev,
                self._key,
                tokens,
                masks,
                bads,
                counts,
            ) = fn(
                self._variables, self._cache, self._last_logits, self._lens,
                self._active_dev, self._remaining_dev, self._key,
                self._temp_dev, self._top_k_dev, self._top_p_dev,
            )
        return tokens, masks, bads, lookahead, counts

    def step(self, lookahead: int = 1) -> List[StepEvent]:  # graftlint: hot-path
        """Decode for every active slot; returns per-slot events.

        :param lookahead: number of decode steps fused into ONE device program and
            ONE host sync (``lax.scan``). The burst emits exactly what ``lookahead``
            sequential calls would — slot retirement (eos / budget / cache room)
            runs inside the scan — at 1/lookahead the host-sync overhead. The
            trade-off is token delivery latency: streamed tokens arrive in bursts.
            Clamped to the largest useful depth for the current slots; compiled
            once per distinct depth.

        With ``pipeline=True`` (the default) each call DISPATCHES the next
        step/burst *before* fetching the previous one's tokens: the device runs
        step N+1 while the host applies step N's tokens, admits requests, and
        fans out events — so events arrive one call later than the dispatch
        that produced them, and the device never idles on host scheduling.
        Retirement runs inside the compiled step either way, so pipelined and
        unpipelined engines emit identical streams (greedy and fixed-seed
        sampled) under identical call schedules.

        A device failure mid-step FAILS the engine (see :meth:`_on_failure`):
        salvage is captured for a supervising batcher, the device state is
        rebuilt in place from host-retained params, and the exception
        re-raises — the engine stays usable either way.
        """
        self._ensure_usable()
        timeline = self.timeline
        timeline.enter("plan")
        events: List[StepEvent] = []
        if self._pending_events:
            # replayed by an out-of-band flush (cancel / contended admission):
            # deliver them FIRST — they predate anything this tick produces
            events.extend(self._pending_events)
            self._pending_events.clear()
        if self._partials:
            # chunked prefills advance one chunk per tick, between decode
            # dispatches, so long prompts never stall the in-flight batch;
            # per-slot chunk failures are absorbed inside (only a poisoning
            # slot-insert failure reaches this handler)
            try:
                self._advance_partials()
            except Exception:
                self._on_failure()
                raise
            timeline.enter("plan")
        if not self._active.any():
            return events
        lookahead = max(1, int(lookahead))
        # host-side accounting of the dispatched-but-unfetched burst: the host
        # mirrors lag it, so depth planning subtracts its steps
        inflight_steps = self._inflight[3] if self._inflight is not None else 0
        room = np.minimum(
            self._remaining[self._active],
            (self.max_len - 1) - self._lens_host[self._active],
        )
        # every active slot runs at least one more step (a slot admitted at the
        # cache-room boundary decodes once and force-finishes), hence the floor
        headroom = max(1, int(room.max())) - inflight_steps
        if headroom <= 0:
            # budget/cache-room retirement is deterministic: every slot the host
            # still thinks active retires within the in-flight burst. Fetch it
            # instead of dispatching a guaranteed-masked step.
            events.extend(self._fetch_inflight())
            return events
        if lookahead > 1:
            # no point scanning past the moment the last slot can retire — but a
            # clamp to the EXACT depth would compile a distinct scan program per
            # tail length, so round up to the next power of two: a bounded ladder
            # of programs (log2 K of them), at most `needed` wasted masked steps
            if headroom < lookahead:
                lookahead = min(lookahead, 1 << (headroom - 1).bit_length())
        # the all-greedy program skips the sampling machinery; heterogeneous slots
        # share the sampling program with per-row controls. Everything the step
        # consumes — activity, budgets, sampling controls — rides as
        # device-resident mirrors (refreshed in _activate/cancel/reset), so a
        # steady-state tick performs ZERO host→device transfers (pinned by the
        # transfer-guard regression test).
        active = int(np.count_nonzero(self._active))
        live_lens = self._lens_host[self._active]
        live_blocks = int(np.sum(live_lens // self._prefix_block_size + 1)) if self.paged else 0
        kernel_steps = self._kernel_steps() if self.paged_attn_impl == "pallas" else 0
        timeline.enter("dispatch", active=active)
        device_was_idle = self._inflight is None
        try:
            tokens, masks, bads, lookahead, counts = self._dispatch_step(lookahead)
        except Exception:
            self._on_failure()
            raise
        self.last_heartbeat = time.monotonic()
        if self._faults is not None:
            for bad_slot in self._faults.take_nan_slots():
                # poison the slot's NEXT sampling input: the following step's
                # in-program finiteness flag trips and the host quarantines it
                self._last_logits = self._last_logits.at[bad_slot].set(jnp.nan)
        self.step_dispatches += 1
        self.active_slot_steps += active * lookahead
        self.live_block_steps += live_blocks * lookahead
        self.live_token_steps += int(live_lens.sum()) * lookahead
        self.kernel_grid_steps += kernel_steps * lookahead
        if device_was_idle and self._last_fetch_done is not None:
            self.idle_dispatches += 1
        previous, prev_skip = self._inflight, self._inflight_skip
        self._inflight, self._inflight_skip = (tokens, masks, bads, lookahead, counts), set()
        if previous is not None:
            # dispatch-ahead: the new step is already queued on the device
            # while the host blocks on (and then applies) the previous one
            events.extend(self._replay_burst(previous, prev_skip))
        if not self.pipeline:
            events.extend(self._fetch_inflight())  # blocks until the burst's tokens are on the host
        return events

    def _kernel_steps(self) -> int:
        """Steps the decode kernel takes in one layer of the step about to be
        dispatched, from the host's mirrors: every slot's row at its length, a
        retired one on the sentinel (``_decode_body_paged``). The call's shapes
        are bound once a table geometry (this runs every dispatch, on the loop
        thread)."""
        geometry = (self._table_width, self._prefix_block_size)
        if self._walk is None or self._walk[0] != geometry:
            from unionml_tpu.ops.paged_attention import walk_steps
            from unionml_tpu.parallel.mesh import TENSOR_AXIS

            layer = {
                name: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype)
                for name, leaf in next(iter(self._layout.paged(self._pool).values())).items()
            }
            k = layer.get("kv", layer.get("k"))
            heads = self._layout.kernel_key[0]
            self._walk = geometry, functools.partial(
                walk_steps,
                jax.ShapeDtypeStruct((self.num_slots, heads, 1, k.shape[-1]), k.dtype),
                k, layer.get("v"), jax.ShapeDtypeStruct((self.num_slots, geometry[0]), jnp.int32),
                k_scale=layer.get("k_scale"), v_scale=layer.get("v_scale"),
                shards=int(self._mesh.shape.get(TENSOR_AXIS, 1)) if self._mesh is not None else 1,
            )
        sentinel = (geometry[0] - 1) * geometry[1]
        return self._walk[1](base_positions=np.where(self._active, self._lens_host, sentinel))

    def abort_all(self) -> None:
        """Deactivate every slot (in-flight state is abandoned; cache reuse is safe).

        A dispatched-but-unfetched pipelined step is DISCARDED, not flushed:
        every request it could emit for is being abandoned, so fetching it
        would only manufacture events with no consumer. The device slot
        mirrors re-upload from the (now all-inactive) host arrays — legal
        precisely because the pipeline is empty.
        """
        self._inflight = None
        self._inflight_skip = set()
        self._pending_events.clear()
        self.discard_salvage()
        self._active[:] = False
        self._reserved[:] = False
        self._partials.clear()
        for slot in list(self._slot_path):
            self._release_prefix(slot)
        if self.paged:
            for slot in list(self._slot_block_map):
                self._free_slot_blocks(slot)
        self._slot_tokens.clear()
        self._slot_queue_wait.clear()
        self._slot_rid.clear()
        self._slot_pending_spans.clear()
        self._remaining[:] = 0
        self._sync_slot_mirrors()

    def cancel(self, slot: int) -> None:
        """Deactivate one slot (its request is abandoned; the slot is reusable).

        With a pipelined step in flight the engine FLUSHES it first: the step
        was dispatched while this slot (and its neighbors) were still live, so
        its tokens must be applied under the OLD slot mapping — deferring the
        fetch past a readmission would credit the stale token to the slot's
        next occupant. Survivors' flushed events are delivered by the next
        :meth:`step`; the cancelled slot's device mirror is then point-updated
        to inactive so the device stops decoding it.
        """
        self._ensure_usable()
        self._flush_inflight()
        # the flush may have buffered this slot's own tokens: its consumer is
        # gone, and delivering them later could credit them to the slot's NEXT
        # occupant — drop them (survivors' events stay queued)
        self._pending_events = [ev for ev in self._pending_events if ev.slot != slot]
        self._active[slot] = False
        self._reserved[slot] = False
        self._remaining[slot] = 0
        self._slot_temp[slot] = self.temperature
        self._slot_top_k[slot] = 0
        self._slot_top_p[slot] = 1.0
        self._partials.pop(slot, None)
        self._slot_queue_wait.pop(slot, None)
        if self._telemetry is not None:
            self._drop_rid(slot)
        self._release_prefix(slot)
        if self.paged:
            self._free_slot_blocks(slot)  # pipeline flushed above: nothing reads them
        self._slot_device_update(slot, False, 0, self.temperature, 0, 1.0)

    # transfers: kv-pin
    def preempt(self, slot: int) -> Optional[PreemptedSlot]:  # graftlint: off-path (scheduler policy action, not steady-state decode)
        """Checkpoint a RUNNING slot into the prefix cache and free it.

        The preempt-to-prefix-cache primitive the SLO scheduler drives: the
        slot's full transcript (prompt + generated tokens) is indexed into the
        radix tree block-by-block — paged engines ADOPT the slot's own pool
        blocks in place (the checkpoint is pure ownership bookkeeping: no
        re-slicing, no device copy); dense engines device-copy KV only for
        blocks the tree does not already hold — and the resulting node path is
        PINNED against LRU eviction. The slot then deactivates exactly like :meth:`cancel`
        (pipeline flushed first, so the transcript and the delivered token
        stream agree), and the returned :class:`PreemptedSlot` lets the caller
        re-queue the request: re-admitting ``tokens`` as the prompt restores
        the pinned blocks through the ordinary prefix-hit path and pays only a
        suffix prefill. The caller MUST eventually call
        :meth:`release_preempted` — after the resume re-admission (which holds
        its own references by then) or when the request is abandoned.

        Returns ``None`` — leaving the slot untouched and running — when the
        slot retired during the pipeline flush, when no transcript exists
        (cache enabled after this slot was admitted), or when the checkpoint
        would not be re-admissible (pool too full to capture enough blocks for
        a transcript beyond the bucket ladder). Raises ``RuntimeError`` when
        the prefix cache is disabled.
        """
        if self._layout.slot_state:
            raise ValueError(
                "preempt (checkpointing a running slot into the prefix cache) with a cache layout "
                f"that keeps per-slot {' and '.join(self._layout.slot_state)}: the checkpoint is blocks "
                "of keys, and a resumed slot would start from empty state"
            )
        if self.prefix_cache is None:
            raise RuntimeError("preempt requires the prefix cache (prefix_cache_blocks > 0)")
        self._ensure_usable()
        # flush the in-flight step under the OLD slot mapping (same rule as
        # cancel): its tokens are real — they extend this slot's transcript
        # and reach its consumer through the buffered events
        self._flush_inflight()
        if not self._active[slot]:
            return None  # retired during the flush: nothing left to preempt
        transcript = self._slot_tokens.get(slot)
        if transcript is None:
            return None  # cache enabled after admission: no transcript to resume
        valid = int(self._lens_host[slot])
        tokens = np.asarray(transcript[:valid], dtype=np.int32)
        # capture: index every full block of the transcript (prompt + generated),
        # device-copying KV out of the slot's cache rows for the new ones only
        self._extend_index(slot, tokens)
        covered = len(self._slot_path.get(slot, ())) * self._prefix_block_size
        try:
            admissible = covered + self.bucket_for(valid - covered) <= self.max_len
        except ValueError:
            admissible = False
        if self.prefill_chunk is not None and valid < self.max_len:
            admissible = True  # the chunked path re-admits any in-capacity suffix
        if not admissible:
            # a pool too full to capture enough blocks: abandoning the slot
            # would strand the request, so decline — it keeps running and the
            # early-captured blocks simply age out of the tree
            return None
        path = self._slot_path.pop(slot, [])
        self.prefix_cache.pin(path)  # survives LRU + the working-ref release below
        try:
            self.prefix_cache.release(path)
            self._slot_tokens.pop(slot, None)
            if self.paged:
                # NEAR-FREE handoff: the checkpoint's blocks were ADOPTED by
                # the index inside _extend_index above — ownership moved, no
                # dense re-slicing, no device copy. Only the un-adopted
                # leftovers (partial tail, unused budget) return to the pool.
                self._free_slot_blocks(slot)
            self._active[slot] = False
            self._reserved[slot] = False
            self._remaining[slot] = 0
            self._slot_temp[slot] = self.temperature
            self._slot_top_k[slot] = 0
            self._slot_top_p[slot] = 1.0
            self._slot_queue_wait.pop(slot, None)
            self.preempted_requests += 1
            if self._telemetry is not None:
                self._note_span(
                    slot, "preempted",
                    transcript_tokens=int(valid), pinned_blocks=len(path),
                )
                self._telemetry.preemptions_total.inc()
                self._drop_rid(slot)
            self._slot_device_update(slot, False, 0, self.temperature, 0, 1.0)
        except Exception:
            # the checkpoint never reached the caller: drop the eviction pin
            # before propagating, or the blocks stay fenced forever
            self.prefix_cache.unpin(path)
            raise
        return PreemptedSlot(tokens=[int(t) for t in tokens], path=path)

    # owns: kv-pin
    def release_preempted(self, state: PreemptedSlot) -> None:
        """Drop a preempted checkpoint's eviction pin — after its resume
        re-admitted (the new slot holds its own references by then) or when
        the re-queued request was cancelled. Idempotence is the caller's job:
        unpinning twice would free blocks a resume still depends on."""
        if self.prefix_cache is not None and state.path:
            self.prefix_cache.unpin(state.path)

    def generate(
        self,
        prompt_ids: Sequence[int],
        max_new_tokens: int,
        *,
        lookahead: int = 1,
        temperature: Optional[float] = None,
        top_k: int = 0,
        top_p: float = 1.0,
    ) -> List[int]:
        """Single-request convenience driver (tests/scripts): run one request to
        completion on an otherwise-idle engine and return its emitted tokens."""
        slot = self.add_request(
            prompt_ids, max_new_tokens, temperature=temperature, top_k=top_k, top_p=top_p
        )
        out: List[int] = []
        # reserved = chunked prefill still in progress: keep ticking until done
        while self._active[slot] or slot in self._partials:
            for event in self.step(lookahead):
                if event.slot == slot and event.emit:
                    out.append(event.token)
        return out


def _as_engine_failure(
    exc: BaseException, *, reason: str = "engine_failure", retryable: bool = True
) -> EngineFailure:
    """Wrap an arbitrary engine-side exception as the structured failure a
    sink receives — never a bare ``str(exc)`` sink (injected faults keep
    their site slug so chaos tests can assert attribution)."""
    if isinstance(exc, EngineFailure):
        return exc
    site = getattr(exc, "site", None)
    if site is not None:
        reason = f"injected_{site}"
    return EngineFailure(f"{type(exc).__name__}: {exc}", reason=reason, retryable=retryable)


_STREAM_DONE = object()
#: what :meth:`_LoopSink.stage` returns for a call that crosses no thread
_IN_PLACE = object()


class _LoopSink:
    """A sink whose consumer is an asyncio event loop on another thread.

    ``stage`` (worker side) turns a sink call into the value ``deliver`` (loop
    side) applies there, or ``_IN_PLACE`` where nothing has to cross.
    ``emit``, ``finish`` and ``fail`` hand one value over each;
    :meth:`ContinuousBatcher._dispatch_events` stages a step's calls and hands
    them to ``loop`` together (:func:`_deliver_batch`).
    """

    #: set by the consumer when it abandons the request (disconnect/early exit);
    #: the worker cancels the slot instead of delivering to a dead consumer
    cancelled = False

    def __init__(self, loop: asyncio.AbstractEventLoop) -> None:
        self.loop = loop

    def _send(self, method: str, *args: Any) -> None:
        value = self.stage(method, *args)
        if value is not _IN_PLACE:
            self.loop.call_soon_threadsafe(self.deliver, value)

    def emit(self, token: int) -> None:
        self._send("emit", token)

    def finish(self) -> None:
        self._send("finish")

    def fail(self, exc: BaseException) -> None:
        self._send("fail", exc)


class _FutureSink(_LoopSink):
    """Buffers emitted tokens; resolves an asyncio future with the full list."""

    def __init__(self, loop: asyncio.AbstractEventLoop, future: asyncio.Future) -> None:
        super().__init__(loop)
        self._future = future
        self._tokens: List[int] = []

    def stage(self, method: str, *args: Any) -> Any:
        if method == "emit":
            self._tokens.append(args[0])
            return _IN_PLACE
        return list(self._tokens) if method == "finish" else args[0]

    def deliver(self, value: Any) -> None:
        if self._future.done():
            return
        if isinstance(value, BaseException):
            self._future.set_exception(value)
        else:
            self._future.set_result(value)


class _QueueSink(_LoopSink):
    """Forwards each token to an asyncio queue as it decodes (streaming)."""

    def __init__(self, loop: asyncio.AbstractEventLoop, queue: "asyncio.Queue") -> None:
        super().__init__(loop)
        self.deliver = queue.put_nowait

    def stage(self, method: str, *args: Any) -> Any:
        return _STREAM_DONE if method == "finish" else args[0]


def _deliver_batch(items: Sequence[Tuple[_LoopSink, Any]]) -> None:
    """Loop side of one hand-over: apply its deliveries in order. One that
    raises is logged and skips none after it."""
    for sink, value in items:
        try:
            sink.deliver(value)
        except Exception:
            logger.warning("sink delivery failed on the consumer's loop", exc_info=True)


class ContinuousBatcher:
    """Asyncio facade running a :class:`DecodeEngine` on a worker thread.

    ``await generate(prompt_ids, max_new_tokens)`` enqueues a request; the worker
    admits queued requests into free slots between decode steps and resolves each
    future with the completed token list. ``stream(...)`` yields tokens as they
    decode instead. One engine step at a time, no step blocking the event loop.

    :param lookahead: decode steps fused per device dispatch (see
        :meth:`DecodeEngine.step`). Raises throughput by cutting host syncs;
        streamed tokens arrive in bursts of up to this size, and queued requests
        wait up to a burst before admission — keep it small (4-16) for
        interactive serving.
    :param scheduler: the SLO admission-control policy
        (:class:`~unionml_tpu.serving.scheduler.SLOScheduler`, or a
        :class:`~unionml_tpu.serving.scheduler.SchedulerConfig` to build one).
        Every request routes through it: bounded multi-class queueing with
        anti-starvation aging, load shedding (structured
        ``QueueFullError``/``DeadlineInfeasibleError``), deadline enforcement
        on queued AND running requests, and — when the engine's prefix cache
        is enabled — preempt-to-prefix-cache for strictly-higher-class
        arrivals against a full house. ``None`` builds the default policy
        (requests without ``priority``/``deadline_ms`` behave like the old
        FIFO queue, now bounded).
    :param supervisor: an
        :class:`~unionml_tpu.serving.supervisor.EngineSupervisor` enabling
        SUPERVISED RECOVERY: on an engine-wide failure every salvageable
        request is checkpoint-resumed through the scheduler (token-identical,
        its sink keeping the tokens already delivered) after an in-place
        engine rebuild — with bounded-exponential-backoff retries and a
        health state machine ``/healthz`` can serve. ``None`` preserves the
        unsupervised contract: in-flight work fails (with structured,
        machine-readable reasons) and the engine auto-recovers for the next
        request.
    """

    #: app-layer capability flag: generate()/stream() accept ``request_id=``
    accepts_request_id = True

    def __init__(
        self,
        engine: DecodeEngine,
        *,
        lookahead: int = 1,
        scheduler: Optional[Any] = None,
        supervisor: Optional[Any] = None,
        telemetry: Optional[Any] = None,
    ) -> None:
        from unionml_tpu.serving.scheduler import SchedulerConfig, SLOScheduler

        self._engine = engine
        #: the engine's phase timeline: this batcher's worker is the loop thread
        self._timeline = engine.timeline
        self._lookahead = max(1, int(lookahead))
        #: span/metrics collector shared by the whole request path; the batcher
        #: is the wiring hub — it propagates one instance into the engine, the
        #: scheduler, the supervisor, the fault plan, and the prefix cache, so
        #: callers only attach telemetry at ONE place (here or the engine)
        self._telemetry = telemetry if telemetry is not None else engine._telemetry
        if self._telemetry is not None:
            if engine._telemetry is None:
                engine._telemetry = self._telemetry
            if engine._faults is not None and engine._faults.telemetry is None:
                engine._faults.telemetry = self._telemetry
            if engine.prefix_cache is not None and engine.prefix_cache.telemetry is None:
                engine.prefix_cache.telemetry = self._telemetry
            if supervisor is not None and getattr(supervisor, "_telemetry", None) is None:
                supervisor._telemetry = self._telemetry
        #: the recovery policy layer (:class:`~unionml_tpu.serving.supervisor.
        #: EngineSupervisor`): with one attached, an engine failure salvages
        #: and RESUMES every recoverable request instead of failing the house;
        #: None preserves the fail-everything-structured behavior
        self.supervisor = supervisor
        if supervisor is not None:
            supervisor.attach(engine)
        #: the SLO admission-control queue (thread-safe: owns its own lock)
        self.scheduler = (
            scheduler
            if isinstance(scheduler, SLOScheduler)
            else SLOScheduler(
                scheduler if isinstance(scheduler, SchedulerConfig) else None,
                telemetry=self._telemetry,
            )
        )
        if self._telemetry is not None and getattr(self.scheduler, "_telemetry", None) is None:
            self.scheduler._telemetry = self._telemetry
        # one signal dict for router + autoscaler: the scheduler's load_signal
        # carries the paged pool's occupancy next to the queue-wait EMAs
        if getattr(self.scheduler, "pool_signal", None) is None:
            self.scheduler.pool_signal = engine.pool_signal
        #: slot -> sink; worker-thread-only by design (admission fan-out and
        #: event dispatch both run on the worker), so no guard is declared
        self._sinks: Dict[int, Any] = {}
        #: slot -> Ticket for the slot's current occupant (deadline enforcement
        #: and preemption-victim choice); worker-thread-only like _sinks
        self._slot_meta: Dict[int, Any] = {}
        self._lock = threading.Lock()
        self._work = threading.Event()
        self._closed = False  # guarded-by: _lock
        #: preempted checkpoints whose tickets died off-worker (close with the
        #: worker live): the worker unpins them, keeping every prefix-cache
        #: mutation on one thread
        self._orphans: List[Any] = []  # guarded-by: _lock
        self._worker: Optional[threading.Thread] = None
        #: fleet hand-off hook: called (worker thread) with this batcher's
        #: orphaned tickets when rebuild exhaustion leaves the engine dead;
        #: returns the tickets it could NOT place elsewhere, which then fail
        #: with the structured unavailable error. None = no fleet (all fail).
        self.on_tickets_orphaned: Optional[Callable[[List[Any]], Sequence[Any]]] = None

    @property
    def engine(self) -> DecodeEngine:
        return self._engine

    def attach_telemetry(self, telemetry: Any) -> None:
        """Wire a span/metrics collector into a PREBUILT batcher (no-op when
        one is already attached): same propagation as construction-time
        wiring, so the app layer instruments prebuilt generators uniformly.
        Call before the first submission — the hooks are read without a lock
        on the assumption they are set before traffic."""
        if telemetry is None or self._telemetry is not None:
            return
        self._telemetry = telemetry  # graftlint: disable=data-race -- documented contract: called before the first submission, so the wiring happens-before every worker read
        engine = self._engine
        if engine._telemetry is None:
            engine._telemetry = telemetry
        if engine._faults is not None and engine._faults.telemetry is None:
            engine._faults.telemetry = telemetry
        if engine.prefix_cache is not None and engine.prefix_cache.telemetry is None:
            engine.prefix_cache.telemetry = telemetry
        if self.supervisor is not None and getattr(self.supervisor, "_telemetry", None) is None:
            self.supervisor._telemetry = telemetry  # graftlint: disable=data-race -- pre-traffic wiring (see docstring); supervisor is never rebound after __init__
        if getattr(self.scheduler, "_telemetry", None) is None:
            self.scheduler._telemetry = telemetry  # graftlint: disable=data-race -- pre-traffic wiring; scheduler is never rebound after __init__ and SLOScheduler guards its own state

    def _ensure_worker(self) -> None:
        if self._worker is None or not self._worker.is_alive():
            self._worker = threading.Thread(target=self._run, name="continuous-batcher", daemon=True)
            self._worker.start()

    def _submit(
        self,
        prompt_ids: Sequence[int],
        max_new_tokens: int,
        sink: Any,
        sampling: Optional[Dict[str, Any]] = None,
        priority: Any = None,
        deadline_ms: Optional[float] = None,
        request_id: Optional[str] = None,
    ) -> None:
        prompt = np.asarray(prompt_ids, dtype=np.int32).reshape(-1)
        # surface bad requests on the caller's side, not the worker's
        if prompt.size == 0:
            raise ValueError("empty prompt")
        self._engine.check_prefillable(int(prompt.size))
        if self.supervisor is not None and self.supervisor.state == "failed":
            # the rebuild budget is exhausted: fail fast with the structured
            # terminal error instead of queueing work that can never run
            raise self.supervisor.unavailable_error()
        ticket = self.scheduler.make_ticket(
            prompt, int(max_new_tokens), sampling, sink,
            priority=priority, deadline_ms=deadline_ms,
        )
        telemetry = self._telemetry
        if telemetry is not None:
            from unionml_tpu.serving.scheduler import class_name

            # joins the fleet-opened trace when request_id is already traced
            # (failover keeps ONE trace across replicas); opens a fresh one
            # for a solo batcher
            ticket.request_id = telemetry.new_trace(
                request_id, cls=class_name(ticket.priority)
            )
            telemetry.note_tokens_in(ticket.request_id, int(prompt.size))
            pool_sig = self._engine.pool_signal()
            telemetry.span(
                ticket.request_id, "admission",
                prompt_tokens=int(prompt.size), budget=int(max_new_tokens),
                cls=class_name(ticket.priority),
                deadline_ms=deadline_ms,
                # journal v2: the pool arithmetic at admission time, so a
                # simulator replay needs no side channels (0 / None on dense)
                block_demand=self._engine.block_demand(
                    int(prompt.size), int(max_new_tokens)
                ),
                available_blocks=(
                    None if pool_sig is None else pool_sig["available_blocks"]
                ),
            )
        try:
            with self._lock:
                if self._closed:
                    raise EngineFailure("batcher is closed", reason="batcher_closed")
                # shed decisions raise HERE (caller side) while the close check
                # still holds, so a shed request never reaches a closed queue
                displaced = self.scheduler.submit(ticket)
        except Exception as exc:
            if telemetry is not None:
                # terminal shed span + journal entry (429/503 at the route);
                # recorded OUTSIDE both locks (telemetry is lock-leaf)
                reason = getattr(exc, "reason", "rejected")
                telemetry.sheds_total.inc(1.0, reason)
                telemetry.end_trace(ticket.request_id, "shed", reason=reason)
            raise
        if displaced is not None:
            # a full queue displaced its worst request in favor of this one:
            # fail it fast with the structured shed error (sink delivery is
            # thread-safe; displaced tickets are never resumes, so no pin)
            if telemetry is not None:
                telemetry.sheds_total.inc(1.0, "displaced")
                telemetry.end_trace(displaced.request_id, "shed", reason="displaced")
            self._deliver(displaced.sink, "fail", displaced.shed_exc)
        self._ensure_worker()
        self._work.set()

    def adopt_ticket(self, ticket: Any) -> None:
        """Adopt another batcher's orphaned ticket (fleet failover).

        The ticket arrives re-routed from a replica whose rebuild budget
        exhausted: its prompt is already the full transcript, its budget the
        unspent remainder, its deadline/priority/sink untouched, and its
        salvage pin released (pins never cross engines — this engine pays a
        fresh prefill, shortened by whatever prefix its own cache holds).
        Sinks are loop-bound, not engine-bound, so delivery continues
        seamlessly. Requeues through the scheduler's salvage path (bypassing
        the admission bound — the work is already partially paid for) and
        raises :class:`~unionml_tpu.serving.faults.EngineFailure` when this
        batcher is closed, so the caller can try the next survivor.
        """
        prompt = np.asarray(ticket.prompt, dtype=np.int32).reshape(-1)
        self._engine.check_prefillable(int(prompt.size))  # unroutable here -> caller tries elsewhere
        with self._lock:
            if self._closed:
                raise EngineFailure("batcher is closed", reason="batcher_closed")
            self.scheduler.requeue(ticket, preemption=False)
        self._ensure_worker()
        self._work.set()

    async def generate(
        self,
        prompt_ids: Sequence[int],
        max_new_tokens: int,
        *,
        priority: Any = None,
        deadline_ms: Optional[float] = None,
        request_id: Optional[str] = None,
        **sampling,
    ) -> List[int]:
        loop = asyncio.get_running_loop()
        future: asyncio.Future = loop.create_future()
        self._submit(
            prompt_ids, max_new_tokens, _FutureSink(loop, future), sampling,
            priority=priority, deadline_ms=deadline_ms, request_id=request_id,
        )
        return await future

    async def stream(
        self,
        prompt_ids: Sequence[int],
        max_new_tokens: int,
        *,
        priority: Any = None,
        deadline_ms: Optional[float] = None,
        request_id: Optional[str] = None,
        **sampling,
    ):
        """Async iterator of tokens, yielded as the engine decodes them.

        The request shares slots (and decode steps) with every other in-flight
        request; per-token latency is one engine step. Abandoning the iterator
        early (client disconnect) cancels the request's decode slot.
        """
        loop = asyncio.get_running_loop()
        queue: "asyncio.Queue" = asyncio.Queue()
        sink = _QueueSink(loop, queue)
        self._submit(
            prompt_ids, max_new_tokens, sink, sampling,
            priority=priority, deadline_ms=deadline_ms, request_id=request_id,
        )
        try:
            while True:
                item = await queue.get()
                if item is _STREAM_DONE:
                    return
                if isinstance(item, BaseException):
                    raise item
                yield item
        finally:
            # reached on normal completion too (cancelling a finished request
            # is a no-op); on early exit it frees the slot for other requests
            sink.cancelled = True

    def _deliver(self, sink: Any, method: str, *args) -> bool:
        """Invoke a sink callback, absorbing consumer-side failures.

        A dead consumer (its event loop closed after a disconnect/early exit)
        raises from ``call_soon_threadsafe``; that must cost only this request —
        never the worker thread, which every other in-flight request depends on.
        """
        try:
            getattr(sink, method)(*args)
            return True
        except Exception:
            logger.warning("sink %s delivery failed (consumer gone?); dropping request", method)
            return False

    # owns: kv-pin
    def _release_ticket(self, ticket: Any) -> None:
        """Drop a dead ticket's engine-side state: a preempted checkpoint's
        eviction pin must not outlive its request (worker thread only)."""
        if ticket.resume is not None:
            self._engine.release_preempted(ticket.resume)
            ticket.resume = None

    # owns: trace
    def _tel_end(self, ticket: Any, status: str, reason: Optional[str] = None) -> None:
        """Close a ticket's trace on terminal delivery (no-op without telemetry
        or for untraced tickets; always called OUTSIDE the batcher lock)."""
        if self._telemetry is None or getattr(ticket, "request_id", None) is None:
            return
        if status == "shed" and reason is not None:
            self._telemetry.sheds_total.inc(1.0, reason)
        self._telemetry.end_trace(ticket.request_id, status, reason=reason)

    def _drain_orphans(self) -> None:
        """Unpin checkpoints whose tickets were dropped off-worker (close)."""
        with self._lock:
            orphans, self._orphans[:] = list(self._orphans), []
        for state in orphans:
            self._engine.release_preempted(state)

    def _enforce_deadlines(self) -> None:  # graftlint: off-path (scheduler policy, not steady-state decode)
        """Fail queued tickets and cancel running slots whose deadline passed.

        A request that can no longer meet its SLO only burns decode steps and
        queue positions other requests need — both sides fail fast with the
        structured :class:`DeadlineExceededError` (HTTP 504 at the route).
        """
        from unionml_tpu.serving.scheduler import DeadlineExceededError

        now = time.monotonic()
        for ticket in self.scheduler.take_expired(now):
            self._release_ticket(ticket)
            self._tel_end(ticket, "shed", "deadline_exceeded")
            self._deliver(
                ticket.sink, "fail",
                DeadlineExceededError("deadline expired while queued"),
            )
        for slot, ticket in list(self._slot_meta.items()):
            if ticket.expired(now):
                # cancel flushes the pipeline and drops this slot's own
                # buffered tokens; survivors' events are delivered by the
                # next step under the unchanged mapping
                self._engine.cancel(slot)
                self.scheduler.note_deadline_miss_running()
                self._sinks.pop(slot, None)  # graftlint: disable=data-race -- _sinks is worker-thread-only by design (declared at __init__); the api-side accesses are drain/close idle probes that tolerate staleness
                self._slot_meta.pop(slot, None)  # graftlint: disable=data-race -- worker-thread-only like _sinks (declared at __init__); tests drive _admit synchronously with no worker running
                self._tel_end(ticket, "shed", "deadline_exceeded")
                self._deliver(
                    ticket.sink, "fail",
                    DeadlineExceededError("deadline expired while decoding"),
                )

    def _maybe_preempt(self) -> None:  # graftlint: off-path (scheduler policy, not steady-state decode)
        """Preempt-to-prefix-cache: when a strictly-higher-class request waits
        with no free slot, checkpoint the worst running victim (lowest class,
        most tokens remaining) into the prefix cache, and re-queue it so its
        resume pays only a suffix prefill. One victim per admission round —
        the freed slot goes to the waiter before any further preemption."""
        if (
            self.scheduler.config.fifo
            or not self.scheduler.config.preempt
            or not self._engine.preemptible
        ):
            return
        if self._engine.free_slots and not self._block_starved():
            return
        waiting = self.scheduler.best_waiting_priority()
        if waiting is None:
            return
        # victims: strictly lower class than the waiter, worst class first,
        # most remaining tokens first (least sunk work per token reclaimed)
        victims = sorted(
            (
                (ticket.priority, int(self._engine._remaining[slot]), slot, ticket)
                for slot, ticket in self._slot_meta.items()
                if ticket.priority > waiting and self._engine._active[slot]
            ),
            reverse=True,
        )
        for _, _, slot, ticket in victims:
            state = self._engine.preempt(slot)
            try:
                # the preempt flush ran under the OLD mapping: deliver the
                # victim's (and survivors') flushed tokens before re-keying
                self._drain_flush_events()
                if state is None:
                    # retired during the flush (a slot freed anyway) or not
                    # checkpointable — the dispatch above reconciled either way
                    if self._engine.free_slots:
                        return
                    continue
                # the sink keeps every token it already received; the ticket's
                # prompt becomes the full transcript and its budget shrinks by
                # the tokens already delivered, so the resumed decode continues
                # the stream exactly where the preemption cut it
                sink = self._sinks.pop(slot, None)
                meta = self._slot_meta.pop(slot, ticket)
                generated = len(state.tokens) - len(meta.prompt)
                meta.prompt = np.asarray(state.tokens, dtype=np.int32)
                meta.budget = int(meta.budget) - max(0, generated)
                meta.resume = state
                meta.sink = sink if sink is not None else meta.sink
                self.scheduler.requeue(meta)
            except Exception as exc:
                # the checkpoint never reached the queue: drop its pin before
                # propagating, or the victim's blocks stay fenced forever —
                # and fail the victim's consumer (its sink left the slot maps
                # above, so the engine-failure sweep can no longer reach it)
                if state is not None:
                    self._engine.release_preempted(state)
                victim = self._sinks.pop(slot, None) or getattr(
                    ticket, "sink", None
                )
                self._slot_meta.pop(slot, None)
                if victim is not None:
                    self._deliver(victim, "fail", exc)
                self._tel_end(ticket, "error", "preempt_requeue_failed")
                raise
            return

    def _block_starved(self) -> bool:
        """True when the head queued ticket's conservative block demand
        exceeds what the paged pool could allocate right now — the signal
        that block pressure (not slot scarcity) is gating admission, which
        arms preempt-to-prefix-cache even with slots free. Always False on
        dense engines (no block accounting)."""
        avail = getattr(self._engine, "available_blocks", lambda: None)()
        if avail is None:
            return False
        head = self.scheduler.peek()
        if head is None:
            return False
        return self._engine.block_demand(len(head.prompt), head.budget) > avail

    def _admit(self) -> None:  # graftlint: off-path (admission, not steady-state decode)
        self._timeline.enter("admit")
        self._drain_orphans()
        self._enforce_deadlines()
        self._maybe_preempt()
        while True:
            free = self._engine.free_slots
            if not free:
                return
            batch = self.scheduler.pop(len(free))
            if not batch:
                return
            admissible = []
            blocked: List[Any] = []
            # paged admission gates on BLOCK demand too: tickets past the
            # pool's reclaimable budget requeue (in scheduler order) instead
            # of bouncing off the engine's pool_exhausted failure — they age
            # in the queue and admit as running requests retire
            avail = getattr(self._engine, "available_blocks", lambda: None)()
            for ticket in batch:
                if blocked:
                    blocked.append(ticket)  # keep scheduler order behind the blocker
                    continue
                if ticket.sink.cancelled:  # consumer gave up while queued
                    self._release_ticket(ticket)
                    self._tel_end(ticket, "cancelled")
                    continue
                try:
                    self._engine.validate_request(ticket.prompt, ticket.budget, **ticket.sampling)
                except Exception as exc:  # reject this request, keep serving others
                    self._release_ticket(ticket)
                    self._tel_end(ticket, "error", "invalid_request")
                    self._deliver(ticket.sink, "fail", exc)
                    continue
                if avail is not None:
                    demand = self._engine.block_demand(len(ticket.prompt), ticket.budget)
                    if demand > avail:
                        # head-of-line blocking on purpose: admitting smaller
                        # latecomers around a starved head would starve it
                        blocked.append(ticket)
                        continue
                    avail -= demand
                admissible.append(ticket)
            for ticket in blocked:
                self.scheduler.requeue(ticket, preemption=False)
            if admissible and not self._admit_batch(admissible):
                return  # engine failure ended this admission round
            if blocked:
                return  # the pool is the binding constraint: wait for retirements
            if not admissible:
                continue

    def _drain_flush_events(self) -> None:
        """Deliver events an admission-time pipeline flush buffered — under
        the OLD sink mapping, BEFORE any new sink takes over a slot."""
        if getattr(self._engine, "has_pending_events", False):
            self._dispatch_events(self._engine.take_pending_events())
            self._timeline.enter("admit")

    def _engine_admit(self, tickets: Sequence[Any]) -> List[int]:
        """``admit_many`` for ``tickets``; the engine's prefill waves are the
        ``prefill`` phase, and the loop is back in ``admit`` when this returns
        or raises."""
        try:
            return self._engine.admit_many(
                [(t.prompt, t.budget, self._spec_sampling(t)) for t in tickets]
            )
        finally:
            self._timeline.enter("admit")

    def _register(self, slot: int, ticket: Any) -> None:
        """Bind an admitted ticket to its slot (and retire its resume pin:
        the re-admission holds its own references on the blocks now)."""
        self._sinks[slot] = ticket.sink
        self._slot_meta[slot] = ticket
        self._engine.note_queue_wait(slot, ticket.queue_wait_ms)
        if self._telemetry is not None:
            # binds the trace to the slot AND flushes the admission-time
            # prefill/prefix spans the engine buffered for it
            self._engine.note_request_id(slot, ticket.request_id)
            self._telemetry.span(
                ticket.request_id, "admitted",
                slot=slot, resume=ticket.resume is not None,
            )
        if ticket.resume is not None:
            self._engine.release_preempted(ticket.resume)
            ticket.resume = None
        if hasattr(self._engine, "note_request_class"):
            from unionml_tpu.serving.scheduler import class_name

            # label the slot for the per-class acceptance gauge
            self._engine.note_request_class(slot, class_name(ticket.priority))

    def _spec_sampling(self, ticket: Any) -> Optional[Dict[str, Any]]:
        """The ticket's sampling dict with the per-class speculation default
        applied (``SchedulerConfig.speculative_classes``); a client's explicit
        ``speculative`` always wins, and engines without a speculative mode get
        the dict untouched (they reject unknown keys)."""
        if not hasattr(self._engine, "speculation_stats"):
            return ticket.sampling
        from unionml_tpu.serving.scheduler import class_name

        sampling = dict(ticket.sampling or {})
        sampling.setdefault(
            "speculative",
            class_name(ticket.priority) in self.scheduler.config.speculative_classes,
        )
        return sampling

    def _admit_batch(self, admissible: List[Any]) -> bool:  # graftlint: off-path (admission, not steady-state decode)
        """Admit popped tickets with per-request failure attribution.

        One admission call batches same-bucket prefills; when it fails
        WITHOUT an engine failure (the engine rolled this call back cleanly),
        the batch re-admits one request at a time so only the raiser fails —
        with a structured reason — and every sibling proceeds. An engine
        failure hands the un-admitted tickets to the recovery path (they
        requeue untouched) and returns False to end the admission round.
        """
        failures_before = getattr(self._engine, "failure_count", 0)
        try:
            slots = self._engine_admit(admissible)
        except Exception as exc:
            if getattr(self._engine, "failure_count", 0) != failures_before:
                self._handle_engine_failure(exc, pending=admissible)
                return False
            self._drain_flush_events()
            if len(admissible) == 1:
                ticket = admissible[0]
                self._release_ticket(ticket)
                self._tel_end(ticket, "error", "prefill_failed")
                self._deliver(
                    ticket.sink, "fail", _as_engine_failure(exc, reason="prefill_failed")
                )
                return True
            for ticket in admissible:
                failures_before = getattr(self._engine, "failure_count", 0)
                try:
                    (slot,) = self._engine_admit([ticket])
                except Exception as one_exc:
                    if getattr(self._engine, "failure_count", 0) != failures_before:
                        self._handle_engine_failure(one_exc, pending=[ticket])
                        return False
                    self._drain_flush_events()
                    self._release_ticket(ticket)
                    self._tel_end(ticket, "error", "prefill_failed")
                    self._deliver(
                        ticket.sink, "fail",
                        _as_engine_failure(one_exc, reason="prefill_failed"),
                    )
                    continue
                self._drain_flush_events()
                self._register(slot, ticket)
            return True
        self._drain_flush_events()
        for slot, ticket in zip(slots, admissible):
            self._register(slot, ticket)
        return True

    def _fail_all(self, exc: Exception) -> None:  # graftlint: off-path (error path)
        """Fail every in-flight request (structured) and abandon the engine's
        slots — the unsupervised fallback when no recovery policy is attached."""
        failure = _as_engine_failure(exc)
        for ticket in self._slot_meta.values():
            self._tel_end(ticket, "error", failure.reason)
        for sink in self._sinks.values():
            self._deliver(sink, "fail", failure)
        self._sinks.clear()
        self._slot_meta.clear()
        self._engine.abort_all()

    # owns: kv-pin
    def _handle_engine_failure(self, exc: BaseException, pending: Sequence[Any] = ()) -> None:  # graftlint: off-path (error recovery)
        """Recover from an engine-wide failure.

        With a supervisor: every salvageable request becomes a RESUME ticket
        (its sink keeps the tokens already delivered; the transcript becomes
        the prompt, the unspent budget carries over, and the pinned salvage
        path shrinks the re-prefill to a suffix) re-queued through the
        scheduler — deadlines and priorities intact — after the engine is
        confirmed rebuilt (bounded-backoff retries when the in-place rebuild
        failed). Unsalvageable requests fail with a structured, machine-
        readable reason; rebuild exhaustion fails EVERYTHING (pending,
        resumes, the whole queue) and leaves the supervisor ``failed``.

        Without a supervisor: the old contract — all in-flight work fails,
        now with structured reasons — plus salvage-pin hygiene.

        ``pending`` carries popped-but-unadmitted tickets from a failed
        admission call; they re-queue untouched (no tokens were delivered).
        """
        engine = self._engine
        if hasattr(engine, "note_external_failure"):
            engine.note_external_failure()  # escalate poisoned out-of-band calls
        sup = self.supervisor
        if sup is None:
            if hasattr(engine, "discard_salvage"):
                engine.discard_salvage()
            failure = _as_engine_failure(exc)
            for ticket in pending:
                self._release_ticket(ticket)
                self._tel_end(ticket, "error", failure.reason)
                self._deliver(ticket.sink, "fail", failure)
            self._fail_all(exc)
            return
        sup.note_failure(exc)
        resumes: List[Any] = []
        for rec in (engine.take_salvage() if hasattr(engine, "take_salvage") else []):
            sink = self._sinks.pop(rec.slot, None)
            meta = self._slot_meta.pop(rec.slot, None)
            pin = PreemptedSlot(tokens=list(rec.tokens), path=rec.path)
            if sink is None or meta is None or sink.cancelled:
                engine.release_preempted(pin)  # no consumer: drop the checkpoint
                if meta is not None:
                    self._tel_end(meta, "cancelled")
                continue
            try:
                engine.validate_request(rec.tokens, max(1, int(rec.remaining)), **meta.sampling)
            except Exception as not_resumable:
                engine.release_preempted(pin)
                sup.note_request_failed()
                self._tel_end(meta, "error", "request_unrecoverable")
                self._deliver(
                    sink, "fail",
                    EngineFailure(
                        f"request not resumable after engine failure: {not_resumable}",
                        reason="request_unrecoverable", retryable=False,
                    ),
                )
                if meta.resume is not None:
                    engine.release_preempted(meta.resume)
                    meta.resume = None
                continue
            if meta.resume is not None:
                # preempt-then-failure: the fresher salvage checkpoint
                # supersedes the preemption's — its pin can go now
                engine.release_preempted(meta.resume)
            meta.prompt = np.asarray(rec.tokens, dtype=np.int32)
            meta.budget = int(rec.remaining)
            meta.resume = pin
            meta.sink = sink
            if self._telemetry is not None and meta.request_id is not None:
                # the trace stays OPEN across salvage: continuity from death to
                # resumed decode is exactly what the failover pins assert
                self._telemetry.span(
                    meta.request_id, "salvaged",
                    transcript_tokens=len(rec.tokens), remaining=int(rec.remaining),
                )
            resumes.append(meta)
        # any sink still mapped had nothing salvageable behind it: fail it
        failure = _as_engine_failure(exc)
        for slot, sink in list(self._sinks.items()):
            meta = self._slot_meta.pop(slot, None)
            if meta is not None:
                self._release_ticket(meta)
                self._tel_end(meta, "error", failure.reason)
            sup.note_request_failed()
            self._deliver(sink, "fail", failure)
        self._sinks.clear()
        self._slot_meta.clear()
        if getattr(engine, "failed", False):
            rebuilt = sup.run_rebuild(engine.rebuild)
        else:
            sup.note_rebuilt()  # the engine already rebuilt itself in place
            rebuilt = True
        if not rebuilt:
            # this engine is dead for good. Every ticket's salvage pin points
            # into THIS engine's block pool — a hand-off target can restore
            # nothing from it, and the pins must not outlive the replica — so
            # release them all; the transcript-as-prompt (set above) already
            # carries everything a resume needs on another engine.
            orphans: List[Any] = []
            for meta in resumes:
                if meta.resume is not None:
                    engine.release_preempted(meta.resume)
                    meta.resume = None
                orphans.append(meta)
            for ticket in list(pending) + self.scheduler.drain():
                self._release_ticket(ticket)
                orphans.append(ticket)
            handoff = self.on_tickets_orphaned
            unplaced: Sequence[Any] = orphans
            if handoff is not None and orphans:
                try:
                    unplaced = list(handoff(orphans))
                except Exception:
                    logger.exception("orphaned-ticket hand-off failed; failing all tickets")
                    unplaced = orphans
            placed = len(orphans) - len(unplaced)
            if placed > 0:
                sup.note_recovered(placed)
            unavailable = sup.unavailable_error()
            for ticket in unplaced:
                sup.note_request_failed()
                self._tel_end(ticket, "error", getattr(unavailable, "reason", "engine_failed"))
                self._deliver(ticket.sink, "fail", unavailable)
            return
        for meta in resumes:
            self.scheduler.requeue(meta, preemption=False)
        if resumes:
            sup.note_recovered(len(resumes))
        for ticket in pending:
            self.scheduler.requeue(ticket, preemption=False)

    def _dispatch_events(self, events) -> None:
        """Fan one step's events out to their sinks (cancel on dead consumers;
        engine-terminated requests fail with their structured reason).

        The bookkeeping runs here, on the worker; what crosses to a consumer's
        event loop is staged and handed over once a loop, after the events,
        in their order (one wake-up of the server's thread a step, not one a
        token). Sinks with no event loop are called in place."""
        self._timeline.enter("fan_out")
        batches: Dict[Any, List[Tuple[Any, Any]]] = {}
        for event in events:
            sink = self._sinks.get(event.slot)
            if sink is None:
                continue
            if sink.cancelled:  # consumer abandoned the stream mid-decode
                self._drop_consumer(event.slot, event.finished)
                continue
            if event.error is not None:
                # the engine terminated this request (NaN quarantine, chunked-
                # prefill death): the slot is already free engine-side, so only
                # the consumer-side failure remains to deliver
                del self._sinks[event.slot]
                meta = self._slot_meta.pop(event.slot, None)
                if meta is not None:
                    self._release_ticket(meta)
                    self._tel_end(meta, "error", event.error)
                if self.supervisor is not None:
                    self.supervisor.note_request_failed()
                self._stage(
                    batches, sink, "fail",
                    EngineFailure(
                        f"request terminated by the engine: {event.error}",
                        reason=event.error,
                    ),
                )
                continue
            if event.emit and not self._stage(batches, sink, "emit", event.token):
                self._drop_consumer(event.slot, event.finished)
                continue
            if event.finished:
                del self._sinks[event.slot]
                meta = self._slot_meta.pop(event.slot, None)
                if meta is not None:
                    self._tel_end(meta, "ok")
                self._stage(batches, sink, "finish")
        engine = self._engine
        for loop, items in batches.items():
            try:
                loop.call_soon_threadsafe(_deliver_batch, items)
            except Exception:
                # the loop closed since it was staged: its requests end as a
                # failed delivery would have ended each
                logger.warning("sink hand-over failed (consumer gone?); dropping its requests")
                staged = {sink for sink, _ in items}
                for slot, sink in list(self._sinks.items()):
                    if sink in staged:
                        self._drop_consumer(slot, finished=False)
                continue
            engine.sink_handoffs += 1
            engine.sink_deliveries += len(items)

    def _stage(self, batches: Dict[Any, List[Tuple[Any, Any]]], sink: Any, method: str, *args) -> bool:
        """One sink call of :meth:`_dispatch_events`: in place for a sink with
        no event loop, else staged in ``batches`` for its loop's hand-over.
        False where the consumer is gone (a closed loop, a call that raised)."""
        loop = getattr(sink, "loop", None)
        if loop is None:
            return self._deliver(sink, method, *args)
        value = sink.stage(method, *args)
        if value is _IN_PLACE:
            return True
        items = batches.get(loop)
        if items is None:
            if loop.is_closed():
                logger.warning("sink %s delivery failed (consumer gone?); dropping request", method)
                return False
            items = batches[loop] = []
        items.append((sink, value))
        return True

    def _drop_consumer(self, slot: int, finished: bool) -> None:
        """Forget a request whose consumer is gone: sink and meta go, its trace
        ends "cancelled". A FINISHED event's slot already retired engine-side —
        and may even hold a newly admitted request by the time a pipeline-
        flushed event is delivered, so cancelling it would kill the wrong
        occupant. Only a still-running slot needs the cancel."""
        del self._sinks[slot]
        meta = self._slot_meta.pop(slot, None)
        if meta is not None:
            self._tel_end(meta, "cancelled")
        if not finished:
            self._engine.cancel(slot)

    def _run(self) -> None:  # graftlint: hot-path
        while True:
            with self._lock:
                done = self._closed and not self.scheduler.depth and not self._sinks
            if done:
                self._drain_orphans()
                self._timeline.leave()
                return
            try:
                self._admit()
            except Exception as exc:
                # _admit handles admission failures itself; what lands here is
                # scheduler-policy engine work (deadline cancel, preempt) dying
                logger.exception("admission round failed")
                self._handle_engine_failure(exc)
                continue
            if self._engine.num_active == 0 and (
                self._engine.has_pending_prefill
                or getattr(self._engine, "has_pending_events", False)
            ):
                # chunked prefills need ticks even with nothing decoding, and a
                # pipeline flush (cancel path) may have buffered events whose
                # sinks are still waiting
                try:
                    events = self._engine.step()
                except Exception as exc:
                    logger.exception("chunked-prefill tick failed")
                    self._handle_engine_failure(exc)
                    continue
                self._dispatch_events(events)
                continue
            if self._engine.num_active == 0:
                self._timeline.enter("idle")
                self._work.clear()
                # re-check under the flag: a request may have landed just now.
                # The bounded 0.5s wait doubles as the deadline-expiry tick for
                # queued requests while the engine idles.
                with self._lock:
                    if self.scheduler.depth or self._closed:
                        continue
                self._work.wait(timeout=0.5)
                continue
            try:
                # full house + queued work: shorten bursts so a retiring slot is
                # readmitted within a few steps — but not to 1, which would forfeit
                # the whole lookahead win for the entire duration of an overload
                contended = bool(self.scheduler.depth) and not self._engine.free_slots
                events = self._engine.step(
                    min(self._lookahead, 4) if contended else self._lookahead
                )
            except Exception as exc:  # recover (supervised) or fail loudly
                logger.exception("continuous-batching step failed")
                self._handle_engine_failure(exc)
                continue
            self._dispatch_events(events)

    def drain(self, timeout_s: float = 5.0) -> None:
        """Graceful shutdown, phase one: stop admitting NEW submissions (they
        fail fast with the structured ``batcher_closed`` error) while queued
        and running requests keep decoding to completion, for up to
        ``timeout_s``. Whatever remains after the window is failed promptly by
        the :meth:`close` this ends with — a bounded drain, never a hang."""
        with self._lock:
            self._closed = True
        self._work.set()
        deadline = time.monotonic() + max(0.0, float(timeout_s))
        while time.monotonic() < deadline:
            worker = self._worker
            if worker is None or not worker.is_alive():
                break  # nothing in flight can make progress anyway
            # advisory cross-thread reads: the worker owns these, but a stale
            # read only costs one extra 20ms poll
            if not self.scheduler.depth and not self._sinks and self._engine.num_active == 0:
                break
            time.sleep(0.02)
        self.close()

    def close(self) -> None:
        """Shut the batcher down: every still-QUEUED request fails promptly
        with the structured ``batcher_closed`` error (futures/streams must
        never hang on a closed batcher), running requests drain, and the
        worker exits. Preempted checkpoints of failed tickets are unpinned on
        the worker thread (the only prefix-cache mutator) when it is alive."""
        with self._lock:
            self._closed = True
        closed_exc = EngineFailure("batcher closed", reason="batcher_closed")
        orphans: List[Any] = []
        for ticket in self.scheduler.drain():
            if ticket.resume is not None:
                orphans.append(ticket.resume)
                ticket.resume = None
            self._tel_end(ticket, "shed", "batcher_closed")
            self._deliver(ticket.sink, "fail", closed_exc)
        worker = self._worker
        if orphans:
            if worker is not None and worker.is_alive():
                with self._lock:
                    self._orphans.extend(orphans)
            else:
                for state in orphans:
                    self._engine.release_preempted(state)
        self._work.set()
        if worker is not None:
            worker.join(timeout=5.0)
            if not worker.is_alive():
                # the worker exited without its final pass (e.g. it died on an
                # engine failure before close): nothing else touches the cache
                # now, so the orphaned pins can drop here
                self._drain_orphans()
        if self.supervisor is not None:
            self.supervisor.close()

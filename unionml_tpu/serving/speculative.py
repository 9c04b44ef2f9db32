"""Speculative decoding for the production paged engine (and a legacy facade).

Two generations live here:

- :class:`SpeculativeEngine` — speculative decoding as a first-class MODE of
  the continuous-batching :class:`~unionml_tpu.serving.continuous.DecodeEngine`
  (ISSUE 16). Draft and target share ONE block-table/allocator/id space: the
  draft's K/V lives in a parallel set of pool leaves indexed by the same block
  ids, so prefix-cache splices, preempt-to-cache, salvage, and failover apply
  to speculative requests with zero new block accounting. Rounds (propose-γ +
  verify + accept/commit + adaptive-γ update) are ONE jitted program that
  dispatches ahead exactly like the PR-3 pipeline and pays one deferred fetch —
  zero steady-state host→device uploads. γ adapts per request from an
  acceptance EMA, decaying to 0 (≈ vanilla) on adversarial traffic.

- :class:`SpeculativeBatcher` — the legacy single-stream ``/generate`` facade
  over :func:`unionml_tpu.models.speculative.speculative_generate` (dense
  caches, fixed γ, batch-1 verify). Kept for apps that want the zero-setup
  latency play; everything throughput-shaped should use the engine mode.

Why the engine's rounds are EXACT (token-identical to vanilla decode, greedy
and fixed-seed sampled): every token selection — the round's bonus token, the
draft's proposals, and the target's per-position choices — goes through ONE
selection rule keyed by ``fold_in(slot_key, position)``. A proposal is
accepted iff it EQUALS the target's own selection at that position, so the
emitted stream is, position by position, exactly the sequence the target
alone would have selected; the draft merely prepays verification compute
(common random numbers make the draft agree often, which is where the
accepted-tokens-per-target-step > 1 comes from). The carried ``last_logits``
always follows the last FED token, and the commit writes exactly the emitted
tokens — so the pool trajectory matches vanilla decode byte-for-byte on fp32
pools (int8 pools ride the pinned divergence budget vs the PLAIN engine, and
are bitwise between spec-on and spec-off arms, which share this program).
"""

import asyncio
import threading
import time
from types import SimpleNamespace
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from unionml_tpu._logging import logger
from unionml_tpu.serving.continuous import DecodeEngine, bind_serving_mesh

__all__ = ["SpeculativeBatcher", "SpeculativeEngine"]


class SpeculativeEngine(DecodeEngine):
    """Continuous-batching decode engine with adaptive speculative rounds.

    A drop-in :class:`DecodeEngine` (paged mode required) that additionally
    holds a DRAFT model whose K/V rides the same block tables as the target's:
    ``self._draft_pool`` is a second set of pool leaves (draft shapes, same
    block ids), so allocation, splice, preempt, salvage, and failover stay
    oblivious to speculation. Requests opt in per admission via the sampling
    dict — ``{"speculative": True, "seed": ..., "gamma": ...}`` — which the
    SLO scheduler sets per class (interactive on, batch off).

    **Round program.** When any active slot is speculative (or samples — keyed
    selection needs the round program either way), :meth:`_dispatch_step`
    swaps the base burst for ONE jitted round: select the bonus token e0 from
    ``last_logits``; draft-propose up to ``gamma_max`` continuations (common
    keyed selection); verify the S = ``gamma_max``+1 chunk through the paged
    verify kernel (pool untouched — :func:`unionml_tpu.models.gpt.
    _paged_verify_chunk`); accept the longest prefix of proposals that equal
    the target's own selections; emit ``a+1`` tokens through the standard
    (tokens, masks, bads) burst contract with the vanilla retirement rule
    inlined per emission; commit exactly the emitted tokens
    (:func:`~unionml_tpu.models.gpt.paged_commit_chunk` — no γ block slack:
    draft overshoot lands in the scratch column); and update the per-slot
    acceptance EMA and γ device-side. The host replays the fetched masks to
    mirror the EMA/γ rule (retiring slots mis-estimate their last round,
    harmlessly — they re-arm at next admission).

    **Per-request γ=0 is sticky** until the slot re-arms: collapsed acceptance
    degrades a request to vanilla decode (1 emitted token per round, always ≥
    the baseline in accepted-tokens-per-target-step) rather than oscillating.

    **Key discipline.** Rounds never consume the engine's global PRNG key:
    sampled selection is (slot_key, position)-keyed, so token streams are
    independent of dispatch boundaries, pipelining, and sibling admissions.
    The base replay's ``_key_steps`` bookkeeping overcounts splits that round
    bursts never performed; this is harmless because no spec-engine sampled
    stream reads the global key (greedy streams never did).

    **Not supported:** ``top_k``/``top_p`` (engine-wide — any sampling slot
    routes every burst through the round program, whose keyed selection
    implements temperature only), dense (non-paged) mode, and speculation on
    chunked-prefill admissions (the request decodes vanilla instead).
    """

    def __init__(
        self,
        model: Any,
        variables: Any,
        draft: Any,
        draft_variables: Any,
        *,
        gamma_max: int = 4,
        gamma_init: int = 2,
        ema_beta: float = 0.25,
        ema_hi: float = 0.6,
        ema_lo: float = 0.3,
        **kwargs: Any,
    ) -> None:
        if not kwargs.get("paged", True):
            raise ValueError("SpeculativeEngine requires paged=True (the shared block pool)")
        kwargs["paged"] = True
        if int(gamma_max) < 1:
            raise ValueError(f"gamma_max must be >= 1, got {gamma_max}")
        if not 0 <= int(gamma_init) <= int(gamma_max):
            raise ValueError(f"gamma_init must be in [0, gamma_max], got {gamma_init}")
        if not 0.0 < float(ema_beta) <= 1.0:
            raise ValueError(f"ema_beta must be in (0, 1], got {ema_beta}")
        if not 0.0 <= float(ema_lo) < float(ema_hi) <= 1.0:
            raise ValueError(f"need 0 <= ema_lo < ema_hi <= 1, got lo={ema_lo} hi={ema_hi}")
        from unionml_tpu.models.gpt import KVCacheLayout

        for role, net in (("target", model), ("draft", draft)):
            held = net.cache_layout().slot_state
            if held:
                raise ValueError(
                    f"SpeculativeEngine with a {role} whose cache layout keeps per-slot "
                    f"{' and '.join(held)}: a rejected draft token is rolled back by a length, "
                    "and state that has taken the token in cannot be"
                )
            if not isinstance(net.cache_layout(), KVCacheLayout):
                raise ValueError(
                    f"SpeculativeEngine with a {type(net.cache_layout()).__name__} {role}: the "
                    "verify and commit programs write per-head K and V blocks and no other layout"
                )
        if draft.config.vocab_size != model.config.vocab_size:
            raise ValueError(
                f"draft vocab ({draft.config.vocab_size}) != target vocab "
                f"({model.config.vocab_size}): acceptance compares token ids"
            )
        eff_max_len = int(kwargs.get("max_len") or model.config.max_position_embeddings)
        if draft.config.max_position_embeddings < eff_max_len:
            raise ValueError(
                f"draft max_position_embeddings ({draft.config.max_position_embeddings}) "
                f"< engine max_len ({eff_max_len})"
            )
        # everything _init_device_state (called inside super().__init__) reads
        draft = bind_serving_mesh(draft, kwargs.get("mesh"))
        self._draft_model = draft
        self._draft_config = draft.config
        self._draft_cache_sharding = None
        self._gamma_max = int(gamma_max)
        self._gamma_init = int(gamma_init)
        self._ema_beta = float(ema_beta)
        self._ema_hi = float(ema_hi)
        self._ema_lo = float(ema_lo)

        super().__init__(model, variables, **kwargs)

        # draft params: replicated under a mesh (the draft is small by design;
        # its K/V pool is what scales, and that shards via kv_block_spec below)
        if self._mesh is not None:
            draft_variables = jax.device_put(draft_variables, self._replicated)
        self._draft_variables = draft_variables

        # re-derive the weight-dequant hook (an __init__ local in the base)
        if kwargs.get("quantize") == "int8":
            from unionml_tpu.ops.quant import dequantize_tree

            self._maybe_dequant = dequantize_tree
        else:
            self._maybe_dequant = lambda tree: tree

        #: compiled round programs keyed by the trace-time sampling switch
        self._round_fns: Dict[bool, Any] = {}
        #: per-request class labels (batcher-set) for the acceptance gauge
        self._slot_class: Dict[int, str] = {}
        # lifetime counters (survive rebuilds — they describe served traffic)
        self.spec_rounds = 0  #: round bursts replayed
        self.spec_slot_rounds = 0  #: (slot, round) pairs that ran with γ > 0
        self.spec_proposed = 0  #: draft tokens proposed by ran slot-rounds
        self.spec_accepted = 0  #: proposals accepted by verification
        self.spec_fallback_rounds = 0  #: speculative slots decoding with γ = 0
        self.spec_round_dispatches = 0
        self.draft_prefill_dispatches = 0
        self._spec_admissions = 0  # seeds derived-key arming deterministically

        def _spec_update(gamma, ema, t_prev, keys, slot, g0, e0, t0, key_row):
            """Point-update one slot's speculative device state at arming
            (same pipelining-safe discipline as ``_slot_update``)."""
            return (
                gamma.at[slot].set(g0),
                ema.at[slot].set(e0),
                t_prev.at[slot].set(t0),
                keys.at[slot].set(key_row),
            )

        self._spec_update_fn = jax.jit(_spec_update, donate_argnums=(0, 1, 2, 3))

        def _constrain_draft(tree):
            if self._draft_cache_sharding is None:
                return tree
            return jax.tree_util.tree_map(
                lambda leaf: jax.lax.with_sharding_constraint(leaf, self._draft_cache_sharding),
                tree,
            )

        self._constrain_draft = _constrain_draft

        def _draft_chunk(d_variables, chunk_ids, d_pool, tables, slot, position):
            """Draft full-prompt prefill straight into the slot's SHARED table
            row (the draft twin of ``_paged_chunk``; logits discarded — rounds
            recompute the draft state they need from the committed stream).
            Bucket padding past the prompt writes zeros the round feeds
            overwrite before any attention reads them (feed contiguity)."""
            row = jax.lax.dynamic_slice_in_dim(tables, slot, 1, axis=0)
            cache = {"table": row, **d_pool}
            _, new_cache = draft.apply(d_variables, chunk_ids, cache=cache, position=position)
            return _constrain_draft(
                {name: leaf for name, leaf in new_cache.items() if name != "table"}
            )

        self._draft_chunk_fn = jax.jit(_draft_chunk, donate_argnums=(2,))

    # ------------------------------------------------------------------ round program

    def _make_round(self, sampling: bool):
        """Compile the fused speculative round (see the class docstring for the
        structure). ``sampling`` is the same trace-time switch as the base step
        family: the greedy program is pure argmax everywhere."""
        model, draft = self._model, self._draft_model
        maybe_dequant = self._maybe_dequant
        constrain_draft = self._constrain_draft
        max_len, eos = self.max_len, self.eos_token_id
        S = self._gamma_max + 1
        gamma_max = self._gamma_max
        beta, hi, lo = self._ema_beta, self._ema_hi, self._ema_lo
        cache_sharding = self._cache_sharding

        def constrain(tree):
            if cache_sharding is None:
                return tree
            return jax.tree_util.tree_map(
                lambda leaf: jax.lax.with_sharding_constraint(leaf, cache_sharding), tree
            )

        def _round(
            variables, d_variables, pool, d_pool, tables,
            last_logits, lens, active, remaining, gamma, ema, t_prev, slot_keys, temp,
        ):
            from unionml_tpu.models.gpt import paged_commit_chunk

            variables = maybe_dequant(variables)
            # graftlint: disable=retrace -- trace-time reads, exactly like the base paged programs: a pool re-layout changes leaf/table shapes and forces the retrace that re-reads them
            sentinel = (self._table_width - 1) * self._prefix_block_size

            def select(logits, positions):
                """THE selection rule (bonus, proposals, and verification all
                use it): greedy argmax, or a per-(slot, position) keyed
                categorical at the slot's temperature — so the same position
                always draws the same token regardless of which program (or
                which round boundary) evaluates it."""
                greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                if not sampling:
                    return greedy
                keys = jax.vmap(jax.random.fold_in)(slot_keys, positions.astype(jnp.uint32))
                scaled = logits.astype(jnp.float32) / jnp.maximum(temp, 1e-6)[:, None]
                drawn = jax.vmap(jax.random.categorical)(keys, scaled)
                return jnp.where(temp > 0, drawn.astype(jnp.int32), greedy)

            drafting = active & (gamma > 0)
            e0 = select(last_logits, lens)

            # ---- draft: heal + propose (correctness-free: affects only α) ----
            # heal position lens-1: the previous round's LAST accepted proposal
            # was selected but never fed to the draft, so re-append the last
            # committed token (idempotent when already present — same value,
            # same block, and int8 re-quantization of an identical row is a
            # fixed point of the monotone-scale append)
            dcache = {"table": tables, **d_pool}
            heal_pos = jnp.where(drafting, jnp.maximum(lens - 1, 0), sentinel)
            _, dcache = draft.apply(d_variables, t_prev[:, None], cache=dcache, position=heal_pos)
            dlog, dcache = draft.apply(
                d_variables, e0[:, None], cache=dcache,
                position=jnp.where(drafting, lens, sentinel),
            )
            cur = dlog[:, -1, :]
            props = []
            for j in range(1, S):
                d_j = select(cur, lens + j)
                props.append(d_j)
                if j < S - 1:
                    dlog, dcache = draft.apply(
                        d_variables, d_j[:, None], cache=dcache,
                        position=jnp.where(drafting, lens + j, sentinel),
                    )
                    cur = dlog[:, -1, :]
            new_d_pool = constrain_draft(
                {name: leaf for name, leaf in dcache.items() if name != "table"}
            )

            # ---- verify: one S-token target pass, pool untouched ----
            chunk = jnp.concatenate([e0[:, None]] + [p[:, None] for p in props], axis=1)
            cache = {"table": tables, **pool}
            vlogits, vcache = model.apply(
                variables, chunk, cache=cache,
                position=jnp.where(active, lens, sentinel),
            )

            # ---- accept: longest prefix of proposals matching the target ----
            sel = jnp.stack(
                [select(vlogits[:, j, :], lens + 1 + j) for j in range(S - 1)], axis=1
            )  # target's own choice for position lens+1+j
            ok = (
                (chunk[:, 1:] == sel)
                & (jnp.arange(1, S)[None, :] <= gamma[:, None])
                & active[:, None]
            )
            acc = jnp.cumprod(ok.astype(jnp.int32), axis=1)
            a = acc.sum(axis=1)
            plan = a + 1  # bonus token always emits

            # ---- emit: a+1 tokens under the vanilla retirement rule ----
            act, rem, cur_lens = active, remaining, lens
            toks_rows, mask_rows, bad_rows = [], [], []
            for j in range(S):
                tok = chunk[:, j]
                em = act & (j < plan)
                src = last_logits if j == 0 else vlogits[:, j - 1, :]
                bad_rows.append(~jnp.all(jnp.isfinite(src), axis=-1))
                toks_rows.append(tok)
                mask_rows.append(em)
                new_rem = jnp.where(em, rem - 1, rem)
                new_l = jnp.where(em, jnp.minimum(cur_lens + 1, max_len - 1), cur_lens)
                finished = (new_rem <= 0) | (new_l >= max_len - 1)
                if eos is not None:
                    finished = finished | (tok == eos)
                act = act & ~(em & finished)
                rem, cur_lens = new_rem, new_l
            masks = jnp.stack(mask_rows, axis=0)  # (S, n): the burst contract
            m = masks.astype(jnp.int32).sum(axis=0)  # tokens fed+emitted per row

            # ---- commit exactly the emitted tokens into the target pool ----
            new_pool = {}
            for name in pool:
                layer = {k: v for k, v in vcache[name].items() if k not in ("ck", "cv")}
                new_pool[name] = paged_commit_chunk(
                    layer, tables, lens, m, vcache[name]["ck"], vcache[name]["cv"]
                )
            new_pool = constrain(new_pool)

            # ---- carry: last_logits follows the last fed token ----
            last_idx = jnp.clip(m - 1, 0, S - 1)
            fed = jnp.take_along_axis(vlogits, last_idx[:, None, None], axis=1)[:, 0, :]
            new_last_logits = jnp.where((m > 0)[:, None], fed, last_logits)
            new_t_prev = jnp.where(
                m > 0, jnp.take_along_axis(chunk, last_idx[:, None], axis=1)[:, 0], t_prev
            )

            # ---- adaptive γ from the acceptance EMA (γ=0 is sticky) ----
            alpha = a.astype(jnp.float32) / jnp.maximum(gamma, 1).astype(jnp.float32)
            new_ema = jnp.where(drafting, (1.0 - beta) * ema + beta * alpha, ema)
            bump = (new_ema >= hi).astype(jnp.int32) - (new_ema < lo).astype(jnp.int32)
            new_gamma = jnp.where(drafting, jnp.clip(gamma + bump, 0, gamma_max), gamma)

            return (
                new_pool, new_d_pool, new_last_logits, cur_lens, act, rem,
                new_gamma, new_ema, new_t_prev,
                jnp.stack(toks_rows, axis=0), masks, jnp.stack(bad_rows, axis=0),
            )

        # donate the KV pools, the sampling logits, and the spec carries the
        # round replaces; tables/keys/temp ride as plain inputs (admission-only
        # point updates, same discipline as the base step family)
        return jax.jit(_round, donate_argnums=(2, 3, 5, 9, 10, 11))

    # ------------------------------------------------------------------ device state

    def _init_device_state(self) -> None:
        super()._init_device_state()
        from unionml_tpu.models.gpt import init_block_pool

        if self._mesh is not None and self._draft_cache_sharding is None:
            from jax.sharding import NamedSharding, PartitionSpec

            from unionml_tpu.models.gpt import kv_cache_spec
            from unionml_tpu.parallel.mesh import TENSOR_AXIS

            spec = kv_cache_spec(self._draft_config, tuple(self._mesh.axis_names))
            tensor_size = (
                int(self._mesh.shape[TENSOR_AXIS])
                if TENSOR_AXIS in self._mesh.axis_names
                else 1
            )
            if self._draft_config.num_heads % max(tensor_size, 1) != 0:
                spec = PartitionSpec()  # draft heads don't divide: replicate
            self._draft_cache_sharding = NamedSharding(self._mesh, spec)
        # the draft pool mirrors the target pool block-for-block (same ids,
        # same tables, draft leaf shapes); every draft layer quantizes under
        # kv_quantize — the draft is correctness-free, so no skip list
        d_pool = init_block_pool(
            self._draft_config, self.pool_blocks, self._prefix_block_size,
            kv_quantize=self.kv_quantize,
        )
        gamma = jnp.zeros((self.num_slots,), jnp.int32)
        ema = jnp.ones((self.num_slots,), jnp.float32)
        t_prev = jnp.zeros((self.num_slots,), jnp.int32)
        keys = jnp.zeros((self.num_slots, 2), jnp.uint32)
        if self._mesh is not None:
            d_pool = jax.device_put(d_pool, self._draft_cache_sharding)
            gamma = jax.device_put(gamma, self._replicated)
            ema = jax.device_put(ema, self._replicated)
            t_prev = jax.device_put(t_prev, self._replicated)
            keys = jax.device_put(keys, self._replicated)
        self._draft_pool = d_pool
        self._gamma_dev, self._ema_dev = gamma, ema
        self._tprev_dev, self._keys_dev = t_prev, keys
        # host mirrors of the device EMA/γ rule (replayed from fetched masks)
        self._slot_gamma = np.zeros(self.num_slots, dtype=np.int32)
        self._slot_ema = np.ones(self.num_slots, dtype=np.float32)
        self._slot_spec = np.zeros(self.num_slots, dtype=bool)
        #: id(masks) of in-flight ROUND bursts (vs base bursts) for replay
        self._round_bursts: Dict[int, bool] = {}

    # ------------------------------------------------------------------ admission

    def validate_request(
        self,
        prompt_ids: Sequence[int],
        max_new_tokens: int,
        *,
        speculative: Optional[bool] = None,
        seed: Optional[int] = None,
        gamma: Optional[int] = None,
        **sampling: Any,
    ) -> Tuple[np.ndarray, int, float, int, float]:
        """Base validation plus the speculative-mode restrictions; the spec
        keys (``speculative``/``seed``/``gamma``) are accepted and ignored so
        batcher-side validation can pass the full sampling dict through.

        Note the engine needs NO γ slack in max_len or the block pool: the
        verify pass never writes the pool, the commit writes only emitted
        tokens, and draft overshoot lands in the scratch column — so a request
        admissible to the vanilla engine is admissible here (contrast the
        legacy facade, whose dense working window reserves ``gamma + 1``)."""
        self._reject_unsupported_sampling(sampling)
        return super().validate_request(prompt_ids, max_new_tokens, **sampling)

    @staticmethod
    def _reject_unsupported_sampling(sampling: Dict[str, Any]) -> None:
        if sampling.get("top_k") or sampling.get("top_p") not in (None, 1.0):
            # engine-wide, not per-request: one sampling sibling routes EVERY
            # burst through the round program, whose keyed selection implements
            # temperature only
            raise ValueError(
                "speculative engine supports temperature sampling only (no top_k/top_p)"
            )

    def admit_many(self, requests: Sequence[Tuple]) -> List[int]:
        """Admit requests, peeling the speculative controls from each sampling
        dict BEFORE the base admission (its 5-tuple normalization stays
        untouched), then ARM each admitted slot: point-update its γ/EMA/key
        device rows and run the draft's full-prompt prefill through the shared
        table row. Arming re-runs the WHOLE prompt on the draft even when the
        target admission was a prefix-cache hit — that is the draft-side
        splice: shared spliced blocks get their draft leaves (re)written with
        identical content (idempotent), which also self-heals prefixes donated
        by non-speculative requests that never wrote draft KV."""
        peeled, spec_args = [], []
        for req in requests:
            sampling = dict(req[2]) if len(req) > 2 and req[2] else {}
            spec = bool(sampling.pop("speculative", False))
            seed = sampling.pop("seed", None)
            gamma = sampling.pop("gamma", None)
            self._reject_unsupported_sampling(sampling)
            peeled.append((req[0], req[1], sampling))
            spec_args.append((spec, seed, gamma))
        slots = super().admit_many(peeled)
        try:
            for slot, req, (spec, seed, gamma) in zip(slots, peeled, spec_args):
                prompt = np.asarray(req[0], dtype=np.int32).reshape(-1)
                self._arm_slot(slot, prompt, spec, seed, gamma)
        except Exception:
            # arming dispatches donate spec device state: a failure here is a
            # device failure (the base admission already committed the slots)
            self._on_failure()
            raise
        return slots

    def _arm_slot(
        self, slot: int, prompt: np.ndarray, spec: bool, seed: Optional[int], gamma: Optional[int]
    ) -> None:
        armed = spec and slot not in self._partials
        bucket = None
        if armed:
            try:
                bucket = self.bucket_for(int(prompt.size))
            except ValueError:
                armed = False  # admissible only via prefix/chunk paths: decode vanilla
        g0 = 0
        if armed:
            g0 = self._gamma_init if gamma is None else max(0, min(int(gamma), self._gamma_max))
        self._slot_spec[slot] = armed
        self._slot_gamma[slot] = g0
        self._slot_ema[slot] = 1.0
        if seed is None:
            # deterministic derived key: identical admission sequences (e.g.
            # the two arms of a comparison) draw identical per-slot keys
            seed = self._seed * 1_000_003 + self._spec_admissions
        self._spec_admissions += 1
        key_row = np.array(
            [(int(seed) >> 32) & 0xFFFFFFFF, int(seed) & 0xFFFFFFFF], dtype=np.uint32
        )
        scalars = jax.device_put(
            (np.int32(slot), np.int32(g0), np.float32(1.0), np.int32(prompt[-1]), key_row)
        )
        try:
            (self._gamma_dev, self._ema_dev, self._tprev_dev, self._keys_dev) = (
                self._spec_update_fn(
                    self._gamma_dev, self._ema_dev, self._tprev_dev, self._keys_dev, *scalars
                )
            )
        except Exception:
            self._device_poisoned = True
            raise
        if armed:
            self._draft_prefill(slot, prompt, bucket)

    # transfers: kv-block (draft leaves ride the slot's existing block grant)
    def _draft_prefill(self, slot: int, prompt: np.ndarray, bucket: int) -> None:
        """Write the full prompt's draft K/V through ``slot``'s table row
        (bucket-padded, one dispatch). The draft pool is DONATED: a dispatch
        death poisons the device state like any paged chunk failure."""
        ids = np.zeros((1, bucket), dtype=np.int32)
        ids[0, : prompt.size] = prompt
        try:
            self._draft_pool = self._draft_chunk_fn(
                self._draft_variables, jax.device_put(ids), self._draft_pool,
                self._tables, *jax.device_put((np.int32(slot), np.int32(0))),
            )
        except Exception:
            self._device_poisoned = True
            raise
        self.draft_prefill_dispatches += 1
        if self._telemetry is not None:
            self._note_span(slot, "draft_prefill", tokens=int(prompt.size), bucket=int(bucket))

    def note_request_class(self, slot: int, cls: Optional[str]) -> None:
        """Label ``slot``'s occupant with its SLO class (batcher-set) so the
        acceptance gauge can report per class."""
        if cls is not None:
            self._slot_class[slot] = str(cls)

    # ------------------------------------------------------------------ dispatch/replay

    def _dispatch_step(self, lookahead: int) -> Tuple[Any, Any, Any, int, Dict[str, Any]]:
        """Route to the round program whenever any active slot speculates or
        samples; otherwise the base (all-greedy) burst — whose argmax emissions
        are exactly the round program's greedy selection, so the stream is
        dispatch-kind-independent. A round ignores ``lookahead``: it already
        fuses up to S = ``gamma_max``+1 emissions into one dispatch."""
        run_round = bool((self._active & (self._slot_spec | (self._slot_temp > 0))).any())
        if not run_round:
            return super()._dispatch_step(lookahead)
        sampling = bool((self._slot_temp[self._active] > 0).any())
        fn = self._round_fns.get(sampling)
        if fn is None:
            fn = self._round_fns[sampling] = self._make_round(sampling)
        if self._faults is not None:
            self._faults.check_step_dispatch()
        # graftlint: disable=use-after-donate -- _make_round donates argnums (2, 3, 5, 9, 10, 11): both pools, last_logits, and the spec carries; tables/keys/temp are plain inputs
        (
            self._pool,
            self._draft_pool,
            self._last_logits,
            self._lens,
            self._active_dev,
            self._remaining_dev,
            self._gamma_dev,
            self._ema_dev,
            self._tprev_dev,
            tokens,
            masks,
            bads,
        ) = fn(
            self._variables, self._draft_variables, self._pool, self._draft_pool,
            self._tables, self._last_logits, self._lens, self._active_dev,
            self._remaining_dev, self._gamma_dev, self._ema_dev, self._tprev_dev,
            self._keys_dev, self._temp_dev,
        )
        self._round_bursts[id(masks)] = True
        self.spec_round_dispatches += 1
        return tokens, masks, bads, self._gamma_max + 1, {}  # a round reads no model counters

    def _replay_burst(self, burst, skip=frozenset()):
        """Base replay plus, for round bursts, the host-side mirror of the
        device EMA/γ rule: each clean event per slot is one FED token, so
        ``a = fed - 1`` recovers the acceptance count (a slot that retired
        mid-round under-counts its LAST round only — its spec state dies with
        it). Also feeds the speculation counters, span, and gauges."""
        is_round = bool(self._round_bursts.pop(id(burst[1]), False))
        if not is_round:
            return super()._replay_burst(burst, skip)
        gammas_at_dispatch = self._slot_gamma.copy()
        spec_at_dispatch = self._slot_spec.copy()
        events = super()._replay_burst(burst, skip)
        fed: Dict[int, int] = {}
        for ev in events:
            if ev.error is None:
                fed[ev.slot] = fed.get(ev.slot, 0) + 1
        self.spec_rounds += 1
        telemetry = self._telemetry
        for slot, m in fed.items():
            if not spec_at_dispatch[slot]:
                continue
            g = int(gammas_at_dispatch[slot])
            if g <= 0:
                self.spec_fallback_rounds += 1
                continue
            a = max(0, min(m - 1, g))
            self.spec_slot_rounds += 1
            self.spec_proposed += g
            self.spec_accepted += a
            alpha = a / g
            ema = (1.0 - self._ema_beta) * float(self._slot_ema[slot]) + self._ema_beta * alpha
            self._slot_ema[slot] = ema
            bump = 1 if ema >= self._ema_hi else (-1 if ema < self._ema_lo else 0)
            self._slot_gamma[slot] = min(self._gamma_max, max(0, g + bump))
            if telemetry is not None:
                telemetry.spec_proposed_total.inc(float(g))
                telemetry.spec_accepted_total.inc(float(a))
                self._note_span(slot, "speculation", gamma=g, accepted=a, alpha=round(alpha, 4))
        if telemetry is not None:
            live = self._active & self._slot_spec
            by_class: Dict[str, List[float]] = {}
            for slot in np.flatnonzero(live):
                cls = self._slot_class.get(int(slot), "standard")
                by_class.setdefault(cls, []).append(float(self._slot_ema[int(slot)]))
            for cls, vals in by_class.items():
                telemetry.spec_acceptance.set(sum(vals) / len(vals), cls)
            if live.any():
                telemetry.spec_gamma.set(float(self._slot_gamma[live].mean()))
        return events

    def abort_all(self) -> None:
        super().abort_all()
        # in-flight round bursts were discarded with the pipeline; stale ids
        # must not collide with a future burst's id()
        self._round_bursts.clear()
        self._slot_spec[:] = False
        self._slot_gamma[:] = 0
        self._slot_class.clear()

    # ------------------------------------------------------------------ observability

    def kv_pool_stats(self) -> Dict[str, Any]:
        """Base pool accounting plus the draft leaves: the equal-byte A/B
        contract charges speculation for EVERY byte it keeps resident."""
        stats = super().kv_pool_stats()
        if stats and getattr(self, "_draft_pool", None) is not None:
            from unionml_tpu.models.gpt import kv_pool_bytes

            stored, full = kv_pool_bytes(self._draft_pool, self._draft_config.dtype)
            stats["kv_pool_bytes"] += stored
            stats["kv_pool_bytes_dense_equiv"] += full
            stats["draft_kv_pool_bytes"] = stored
        return stats

    def speculation_stats(self) -> Dict[str, Any]:
        """The ``generation.speculation`` block for ``GET /stats``.

        ``accepted_per_target_step`` counts EVERY armed slot-round as one
        target forward pass — including γ-decayed-to-0 fallback rounds, which
        emit exactly their bonus token — so the ratio is honest about
        adaptive degradation: vanilla decode is 1.0, and a collapsed-α
        workload converges to 1.0 rather than being dropped from the metric."""
        live = self._active & self._slot_spec
        ran = max(1, self.spec_slot_rounds + self.spec_fallback_rounds)
        return {
            "enabled_slots": int(live.sum()),
            "gamma_max": self._gamma_max,
            "rounds": self.spec_rounds,
            "round_dispatches": self.spec_round_dispatches,
            "proposed": self.spec_proposed,
            "accepted": self.spec_accepted,
            "fallback_rounds": self.spec_fallback_rounds,
            "acceptance_ema": (
                round(float(self._slot_ema[live].mean()), 4) if live.any() else None
            ),
            "gamma": round(float(self._slot_gamma[live].mean()), 4) if live.any() else None,
            "accepted_per_target_step": (
                round(
                    (self.spec_accepted + self.spec_slot_rounds + self.spec_fallback_rounds)
                    / ran,
                    4,
                )
                if self.spec_slot_rounds + self.spec_fallback_rounds
                else None
            ),
        }


class SpeculativeBatcher:
    """Single-stream speculative generation behind the ContinuousBatcher contract.

    Requests route through the same SLO scheduler as the continuous engine
    (:mod:`unionml_tpu.serving.scheduler`): bounded queueing with structured
    shedding, priority-ordered turn-taking for the single decode stream, and
    deadline enforcement while queued — so ``GET /stats`` reports one uniform
    scheduler counter set whichever generator backs ``/generate``. (Preemption
    does not apply: the verify loop is batch-1 with no KV checkpoint to steal.)
    """

    def __init__(
        self,
        target: Any,
        target_variables: Any,
        draft: Any,
        draft_variables: Any,
        *,
        gamma: int = 4,
        max_len: Optional[int] = None,
        scheduler: Optional[Any] = None,
        faults: Optional[Any] = None,
    ) -> None:
        from unionml_tpu.serving.scheduler import SchedulerConfig, SLOScheduler

        self._target = target
        self._target_variables = target_variables
        self._draft = draft
        self._draft_variables = draft_variables
        self._gamma = int(gamma)
        #: deterministic fault injection (:class:`~unionml_tpu.serving.faults.
        #: FaultPlan`); None = production (one host branch per request)
        self._faults = faults
        #: requests that died in a speculative round (structured failures)
        self.round_failures = 0  # guarded-by: _lock
        self._max_len = int(max_len or target.config.max_position_embeddings)
        self._lock = threading.Lock()  # serializes device work across requests
        #: SLO admission control shared-shape with ContinuousBatcher (/stats)
        self.scheduler = (
            scheduler
            if isinstance(scheduler, SLOScheduler)
            else SLOScheduler(scheduler if isinstance(scheduler, SchedulerConfig) else None)
        )
        #: turn-taking for the single stream: executor threads wait here until
        #: the scheduler ranks their ticket first and no request is running
        self._turn = threading.Condition()
        self._current: Optional[Any] = None  # guarded-by: _turn
        self._closed = False
        # persistent evolving key (same contract as DecodeEngine): identical
        # sampled requests must NOT return identical completions unless the
        # client pins an explicit seed
        self._key = jax.random.PRNGKey(0)  # guarded-by: _lock
        # the /stats view; num_slots=1 states the single-stream design honestly.
        # check_prefillable is the route's prefill-validation hook: speculation
        # prefills at the exact prompt length (no bucket ladder), so nothing to refuse.
        # requests_admitted / tokens_decoded / prefill_tokens_computed mirror the
        # continuous engine's generation counters, so the stats route reports the
        # same shape whichever generator is plugged in
        # guarded-by: _lock
        self.engine = SimpleNamespace(
            num_slots=1,
            num_active=0,
            max_len=self._max_len,
            check_prefillable=lambda n: None,
            requests_admitted=0,
            tokens_decoded=0,
            prefill_tokens_computed=0,
        )

    # ------------------------------------------------------------------ request path

    def _validate(self, prompt_ids: Sequence[int], max_new_tokens: int, sampling: dict):
        if self._closed:
            raise RuntimeError("SpeculativeBatcher is closed")
        prompt = np.asarray(list(prompt_ids), dtype=np.int32)
        if prompt.ndim != 1 or prompt.size == 0:
            raise ValueError("prompt_ids must be a non-empty 1-D token list")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if prompt.size + max_new_tokens + self._gamma + 1 > self._max_len:
            need = prompt.size + max_new_tokens
            # name the BINDING constraint: a request that already overflows
            # max_len on its own is not a γ problem, and saying "gamma slack"
            # there sends operators tuning the wrong knob
            detail = (
                "the request alone"
                if need > self._max_len
                else (
                    f"the draft working window (gamma={self._gamma} proposals + 1 bonus "
                    f"token may be in flight past the last emitted position)"
                )
            )
            raise ValueError(
                f"prompt ({prompt.size}) + max_new_tokens ({max_new_tokens}) exceeds "
                f"max_len ({self._max_len}) once the speculative round slack is "
                f"reserved: {detail} is the binding constraint; lower max_new_tokens "
                f"or gamma"
            )
        if sampling.get("top_k") or sampling.get("top_p") not in (None, 1.0):
            raise ValueError("speculative decoding supports temperature sampling only (no top_k/top_p)")
        temperature = float(sampling.get("temperature", 0.0) or 0.0)
        seed = sampling.get("seed")
        return prompt, temperature, seed

    def _await_turn(self, ticket) -> None:
        """Block until the scheduler ranks ``ticket`` first and the stream is
        free. Raises the ticket's shed error when a later, higher-class submit
        displaced it, and :class:`DeadlineExceededError` when its deadline
        passes while queued — the same structured rejections the continuous
        path surfaces."""
        from unionml_tpu.serving.scheduler import DeadlineExceededError

        with self._turn:
            while True:
                if self._closed:
                    self.scheduler.remove(ticket)
                    raise RuntimeError("SpeculativeBatcher is closed")
                if ticket.shed_exc is not None:  # displaced under a full queue
                    raise ticket.shed_exc
                if ticket.expired(time.monotonic()):
                    # removes this ticket (and any expired peers — their own
                    # waiting threads raise on their next poll) and counts the
                    # queued deadline misses
                    self.scheduler.take_expired()
                    raise DeadlineExceededError("deadline expired while queued")
                if self._current is None and self.scheduler.peek() is ticket:
                    if not self.scheduler.pop_ticket(ticket):
                        raise RuntimeError("ticket vanished from the scheduler queue")
                    self._current = ticket
                    return
                self._turn.wait(timeout=0.02)

    def _end_turn(self) -> None:
        with self._turn:
            self._current = None
            self._turn.notify_all()

    def _run(self, ticket, prompt: np.ndarray, max_new_tokens: int, temperature: float, seed) -> List[int]:
        self._await_turn(ticket)
        try:
            return self._run_current(prompt, max_new_tokens, temperature, seed)
        finally:
            self._end_turn()

    def _run_current(self, prompt: np.ndarray, max_new_tokens: int, temperature: float, seed) -> List[int]:
        from unionml_tpu.models.speculative import speculative_generate
        from unionml_tpu.serving.faults import EngineFailure

        with self._lock:
            if seed is not None:
                rng = jax.random.PRNGKey(int(seed))
            else:
                self._key, rng = jax.random.split(self._key)
            self.engine.num_active = 1
            self.engine.requests_admitted += 1
            try:
                if self._faults is not None:
                    self._faults.check_speculative_round()
                # graftlint: disable=lock-order -- _lock EXISTS to serialize device work across requests (single-stream design, see class docstring); blocking under it is the design, and _await_turn admits exactly one holder
                out = speculative_generate(
                    self._target,
                    self._target_variables,
                    self._draft,
                    self._draft_variables,
                    jax.device_put(prompt)[None, :],  # explicit: keeps the entry path transfer-guard-clean
                    max_new_tokens,
                    gamma=self._gamma,
                    temperature=temperature,
                    rng=rng,
                )
            except Exception as exc:
                # every round's device state is call-local (no persistent KV or
                # donated engine buffers), so a failure costs exactly this
                # request — structured, and the next request runs clean
                self.round_failures += 1
                logger.warning("speculative round failed: %s", exc)
                raise EngineFailure(
                    f"speculative round failed: {exc}", reason="speculative_round_failed"
                ) from exc
            finally:
                self.engine.num_active = 0
            tokens = [int(t) for t in np.asarray(out)[0, prompt.size :]]
            # counter updates stay under the lock: concurrent requests (each on
            # its own executor thread) race read-modify-write otherwise — the
            # lock-discipline lint finding that motivated this placement
            self.engine.prefill_tokens_computed += int(prompt.size)
            self.engine.tokens_decoded += len(tokens)
        return tokens

    async def generate(
        self,
        prompt_ids: Sequence[int],
        max_new_tokens: int,
        *,
        priority: Any = None,
        deadline_ms: Optional[float] = None,
        **sampling,
    ) -> List[int]:
        prompt, temperature, seed = self._validate(prompt_ids, max_new_tokens, sampling)
        # admission control BEFORE any device work: shed errors (queue full /
        # deadline infeasible) raise here, on the caller's side, exactly like
        # the continuous path
        ticket = self.scheduler.make_ticket(
            prompt, int(max_new_tokens), sampling, None,
            priority=priority, deadline_ms=deadline_ms,
        )
        displaced = self.scheduler.submit(ticket)
        if displaced is not None:
            with self._turn:  # wake the displaced ticket's waiting thread
                self._turn.notify_all()
        loop = asyncio.get_running_loop()
        return await loop.run_in_executor(
            None, self._run, ticket, prompt, max_new_tokens, temperature, seed
        )

    async def stream(
        self,
        prompt_ids: Sequence[int],
        max_new_tokens: int,
        *,
        priority: Any = None,
        deadline_ms: Optional[float] = None,
        **sampling,
    ):
        """Async iterator of tokens. Tokens arrive in one burst at completion:
        speculation verifies whole proposal rounds, so there is no per-token
        decode step to stream from (use the continuous engine for live streams)."""
        for token in await self.generate(
            prompt_ids, max_new_tokens, priority=priority, deadline_ms=deadline_ms, **sampling
        ):
            yield token

    def close(self) -> None:
        self._closed = True
        with self._turn:  # wake queued waiters so they fail promptly, not on poll
            self._turn.notify_all()
        logger.info("SpeculativeBatcher closed.")

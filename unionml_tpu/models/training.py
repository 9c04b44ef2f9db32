"""Compiled training loops for the model zoo: single-chip or mesh-sharded.

This is where the BASELINE "BERT-base fine-tune wall-clock" is won: one jit-compiled
train step (donated state, batch sharded over the mesh's data axis, params optionally
tensor/FSDP-sharded), a static-shape host batch iterator feeding it, step metrics
(loss, step time, tokens/s, achieved MFU), and orbax step checkpointing with
preemption-safe flush.
"""

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax.training import train_state
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from unionml_tpu._logging import logger
from unionml_tpu.ops.losses import cross_entropy_and_accuracy
from unionml_tpu.parallel.mesh import (
    DATA_AXIS,
    batch_axis_size,
    batch_sharding,
    wrapped_row_indices,
)
from unionml_tpu.profiling import PhaseTimeline
from unionml_tpu.utils import configure_compile_cache


class TrainState(train_state.TrainState):
    """flax TrainState + dropout rng folding by step."""

    dropout_rng: jax.Array = None  # type: ignore[assignment]


def create_train_state(
    model: Any,
    params: Any,
    learning_rate: float = 2e-5,
    weight_decay: float = 0.01,
    warmup_steps: int = 0,
    total_steps: int = 10_000,
    max_grad_norm: float = 1.0,
    rng: Optional[jax.Array] = None,
    mu_dtype: Any = None,
) -> TrainState:
    """AdamW + linear warmup/decay + global-norm clipping (the BERT fine-tune recipe).

    ``mu_dtype`` (e.g. ``jnp.bfloat16``) stores adam's FIRST moment in reduced
    precision — the standard optimizer-HBM lever (halves mu traffic; the second
    moment stays f32 for numerical range). Not measured on the chip, so no
    default uses it.
    """
    if warmup_steps > 0:
        schedule = optax.warmup_cosine_decay_schedule(
            init_value=0.0,
            peak_value=learning_rate,
            warmup_steps=warmup_steps,
            decay_steps=max(total_steps, warmup_steps + 1),
        )
    else:
        schedule = learning_rate
    tx = optax.chain(
        optax.clip_by_global_norm(max_grad_norm),
        optax.adamw(schedule, weight_decay=weight_decay, mu_dtype=mu_dtype),
    )
    variables = params if "params" in params else {"params": params}
    return TrainState.create(
        apply_fn=model.apply,
        params=variables["params"],
        tx=tx,
        dropout_rng=rng if rng is not None else jax.random.PRNGKey(0),
    )


def _accumulated_value_and_grad(loss_fn, params, batch, accum: int, dropout_rng, has_aux: bool):
    """Microbatched value-and-grad: mean loss/aux/grads over ``accum`` slices.

    ``loss_fn(params, microbatch, rng)`` runs per slice under ``lax.scan`` — peak
    activation memory is one microbatch's, which is the point (pairs with remat
    for memory-bound configs). Equal slice sizes make the mean-of-means exactly
    the full-batch mean; the optimizer step matches the full-batch step up to
    accumulation-order rounding (which adam's normalization amplifies for
    near-zero gradients).
    """

    def reshape(x):
        if x.shape[0] % accum:
            raise ValueError(
                f"grad_accum={accum} must divide the batch size ({x.shape[0]})"
            )
        return x.reshape(accum, x.shape[0] // accum, *x.shape[1:])

    micro = jax.tree_util.tree_map(reshape, batch)
    grad_fn = jax.value_and_grad(loss_fn, has_aux=has_aux)

    first = jax.tree_util.tree_map(lambda x: x[0], micro)
    out_shapes = jax.eval_shape(grad_fn, params, first, dropout_rng)
    zeros = jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype), out_shapes)

    def body(carry, slice_and_index):
        mb, index = slice_and_index
        out = grad_fn(params, mb, jax.random.fold_in(dropout_rng, index))
        return jax.tree_util.tree_map(jnp.add, carry, out), None

    total, _ = jax.lax.scan(body, zeros, (micro, jnp.arange(accum)))
    return jax.tree_util.tree_map(lambda x: x / accum, total)


def make_classifier_train_step(
    mesh: Optional[Mesh] = None,
    param_spec: Any = None,
    input_signature: Tuple[str, ...] = ("inputs",),
    light_metrics: bool = False,
    grad_accum: int = 1,
) -> Callable:
    """Build the compiled train step ``(state, batch) -> (state, metrics)``.

    ``batch`` is a dict with ``input_signature`` keys + ``"labels"``. With a mesh, the
    batch is sharded over the data axis and the state laid out by ``param_spec``
    (when None, leaves already committed to this mesh keep their layout and the
    rest replicate — see :func:`_wrap_step`); XLA inserts the grad all-reduce
    over ICI.
    ``light_metrics=True`` drops the ``grad_norm`` metric — in principle XLA CSEs it
    against the identical norm inside ``clip_by_global_norm``; whether that holds
    on the chip has not been measured. ``grad_accum=N`` splits each
    batch into N sequential microbatches whose gradients average before the one
    optimizer step — same objective, one-Nth the activation memory.
    """
    if grad_accum < 1:
        raise ValueError(f"grad_accum must be >= 1, got {grad_accum}")

    def train_step(state: TrainState, batch: Dict[str, jax.Array]):
        dropout_rng = jax.random.fold_in(state.dropout_rng, state.step)

        def loss_fn(params, mb, rng):
            logits = state.apply_fn(
                {"params": params},
                *[mb[k] for k in input_signature],
                deterministic=False,
                rngs={"dropout": rng},
            )
            return cross_entropy_and_accuracy(logits, mb["labels"])

        if grad_accum > 1:
            (loss, acc), grads = _accumulated_value_and_grad(
                loss_fn, state.params, batch, grad_accum, dropout_rng, has_aux=True
            )
        else:
            (loss, acc), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                state.params, batch, dropout_rng
            )
        new_state = state.apply_gradients(grads=grads)
        metrics = {"loss": loss, "accuracy": acc}
        if not light_metrics:
            metrics["grad_norm"] = optax.global_norm(grads)
        return new_state, metrics

    return _wrap_step(train_step, mesh, param_spec)


def _wrap_step(train_step: Callable, mesh: Optional[Mesh], param_spec: Any) -> Callable:
    """jit a ``(state, batch) -> (state, metrics)`` step, mesh-sharded when given.

    With ``param_spec=None`` the state sharding is derived from the FIRST state the
    step sees: leaves already laid out on this mesh keep their sharding (e.g. params
    an internal ``shard_map`` committed to the expert axis during init — the
    a2a-MoE case), everything else replicates — the plain-DP default.
    """
    if mesh is None:
        return jax.jit(train_step, donate_argnums=(0,))
    if param_spec is not None:
        state_sharding = jax.tree_util.tree_map(
            lambda spec: NamedSharding(mesh, spec),
            param_spec,
            is_leaf=lambda x: isinstance(x, PartitionSpec),
        )
        return jax.jit(
            train_step,
            in_shardings=(state_sharding, batch_sharding(mesh)),
            donate_argnums=(0,),
        )

    # state sharding unspecified: committed leaves keep their layout (params an
    # internal shard_map bound to the expert axis during init — the a2a-MoE case,
    # whose layout also evolves onto the step's OUTPUT sharding after the first
    # donated call), uncommitted leaves replicate onto the mesh — the plain-DP
    # default an explicit replicated() used to force.
    jitted = jax.jit(
        train_step,
        in_shardings=(None, batch_sharding(mesh)),
        donate_argnums=(0,),
    )
    mesh_devices = set(mesh.devices.flat)

    def call(state, batch):
        # leaves committed to some OTHER device set (a single-device checkpoint
        # restore, an explicit device_put) would make jit raise an
        # incompatible-devices error against the mesh-sharded batch; reshard
        # them onto the mesh up front — the acceptance replicated() used to
        # provide. Leaves already on this mesh (or uncommitted) pass through.
        def place(leaf):
            sharding = getattr(leaf, "sharding", None)
            if sharding is None:  # numpy / scalars: jit replicates them itself
                return leaf
            if set(getattr(sharding, "device_set", mesh_devices)) == mesh_devices:
                return leaf
            return jax.device_put(leaf, NamedSharding(mesh, PartitionSpec()))

        return jitted(jax.tree_util.tree_map(place, state), batch)

    return call


def make_lm_train_step(
    mesh: Optional[Mesh] = None,
    param_spec: Any = None,
    packed: bool = False,
    light_metrics: bool = False,
    grad_accum: int = 1,
    moe_aux: bool = False,
) -> Callable:
    """Compiled causal-LM train step ``(state, batch) -> (state, metrics)``.

    ``batch`` carries ``"input_ids"`` plus, with ``packed=True``, the
    ``"segment_ids"`` from :func:`unionml_tpu.ops.packing.pack_sequences` — the
    model confines attention to same-segment tokens and restarts positions per
    segment, and the loss masks cross-segment transitions
    (:func:`unionml_tpu.models.gpt.lm_loss`). Unpacked batches may carry a
    ``"mask"`` (1 = real token) for plain right-padded LM training.
    ``grad_accum=N`` microbatches each step (see
    :func:`make_classifier_train_step`); note the packed per-row token counts
    vary, so accumulated loss weights microbatches equally, not per-token.
    ``moe_aux=True`` (sparse decoders) folds the sown router losses —
    z-loss + load-balancing (:func:`unionml_tpu.models.moe.collect_aux_losses`)
    — into the objective; without it a sparse model's router trains on the LM
    gradient alone and is free to collapse onto few experts.
    """
    if grad_accum < 1:
        raise ValueError(f"grad_accum must be >= 1, got {grad_accum}")
    from unionml_tpu.models.gpt import lm_loss
    from unionml_tpu.models.moe import collect_aux_losses

    def train_step(state: TrainState, batch: Dict[str, jax.Array]):
        dropout_rng = jax.random.fold_in(state.dropout_rng, state.step)

        def loss_fn(params, mb, rng):
            # strict lookup: a packed step fed a batch without segment ids must
            # fail loudly, not silently train across packed-sequence boundaries
            segment_ids = mb["segment_ids"] if packed else None
            if moe_aux:
                logits, sown = state.apply_fn(
                    {"params": params},
                    mb["input_ids"],
                    deterministic=False,
                    rngs={"dropout": rng},
                    segment_ids=segment_ids,
                    mutable=["intermediates"],
                )
                aux = collect_aux_losses(sown["intermediates"])
            else:
                logits = state.apply_fn(
                    {"params": params},
                    mb["input_ids"],
                    deterministic=False,
                    rngs={"dropout": rng},
                    segment_ids=segment_ids,
                )
                aux = 0.0
            return aux + lm_loss(
                logits, mb["input_ids"], mask=mb.get("mask"), segment_ids=segment_ids
            )

        if grad_accum > 1:
            loss, grads = _accumulated_value_and_grad(
                loss_fn, state.params, batch, grad_accum, dropout_rng, has_aux=False
            )
        else:
            loss, grads = jax.value_and_grad(loss_fn)(state.params, batch, dropout_rng)
        new_state = state.apply_gradients(grads=grads)
        metrics = {"loss": loss}
        if not light_metrics:
            metrics["grad_norm"] = optax.global_norm(grads)
        return new_state, metrics

    return _wrap_step(train_step, mesh, param_spec)


def make_lm_eval_step(packed: bool = False) -> Callable:
    """Compiled causal-LM eval step ``(state, batch) -> metrics``.

    Returns per-token ``loss`` and ``perplexity`` (exp of the masked mean
    next-token cross-entropy) over the batch's real transitions — the LM
    counterpart of :func:`make_classifier_eval_step`, sharing
    :func:`make_lm_train_step`'s batch contract (``input_ids`` plus
    ``segment_ids`` when packed / optional ``mask`` otherwise).
    """
    from unionml_tpu.models.gpt import lm_loss

    def eval_step(state: TrainState, batch: Dict[str, jax.Array]):
        segment_ids = batch["segment_ids"] if packed else None
        logits = state.apply_fn(
            {"params": state.params}, batch["input_ids"], deterministic=True,
            segment_ids=segment_ids,
        )
        loss = lm_loss(logits, batch["input_ids"], mask=batch.get("mask"), segment_ids=segment_ids)
        return {"loss": loss, "perplexity": jnp.exp(loss)}

    return jax.jit(eval_step)


def make_classifier_eval_step(input_signature: Tuple[str, ...] = ("inputs",)) -> Callable:
    def eval_step(state: TrainState, batch: Dict[str, jax.Array]):
        logits = state.apply_fn(
            {"params": state.params}, *[batch[k] for k in input_signature], deterministic=True
        )
        loss, acc = cross_entropy_and_accuracy(logits, batch["labels"])
        return {"loss": loss, "accuracy": acc}

    return jax.jit(eval_step)


@dataclass
class FitResult:
    state: TrainState
    metrics_history: list = field(default_factory=list)
    steps: int = 0
    wall_time_s: float = 0.0
    steps_per_s: float = 0.0
    examples_per_s: float = 0.0
    #: seconds the call spent in each of :data:`FIT_PHASES`; they add up to
    #: the whole call, first (compile) step included
    phase_seconds: Dict[str, float] = field(default_factory=dict)


#: the phases of one :func:`fit` call (spans ``fit.<phase>`` on the profiler's
#: clock, sums in ``FitResult.phase_seconds``): ``start`` entry to the timed
#: loop (loader, checkpoint restore, first batch, first step, blocked on);
#: ``input_wait`` the loop obtaining its next batch (the prefetcher's
#: hand-over, ``device_put``, the fence on the transfer, slot release);
#: ``dispatch`` the call into the step; ``log`` and ``checkpoint`` where they
#: run; ``drain`` the closing ``block_until_ready`` (nothing in the loop waits
#: for a step, so the host runs ahead and the device's backlog is waited for
#: here: slack, not work); ``finish`` checkpoint flush and loader close
FIT_PHASES = ("start", "input_wait", "dispatch", "log", "checkpoint", "drain", "finish")


def dict_batches(
    data: Dict[str, np.ndarray],
    batch_size: int,
    rng: Optional[np.random.Generator] = None,
    mesh: Optional[Mesh] = None,
    drop_remainder: bool = True,
) -> Iterable[Dict[str, np.ndarray]]:
    """Static-shape dict-batch iterator; optionally lays batches onto the mesh."""
    host = {k: np.asarray(v) for k, v in data.items()}
    n_rows = len(next(iter(host.values())))
    indices = np.arange(n_rows) if rng is None else rng.permutation(n_rows)
    end = (n_rows // batch_size) * batch_size if drop_remainder else n_rows
    if end == 0:
        end = n_rows
    sharding = batch_sharding(mesh) if mesh is not None else None
    axis_size = batch_axis_size(mesh) if mesh is not None else 1
    for start in range(0, end, batch_size):
        idx = indices[start : start + batch_size]
        if sharding is not None:
            wrap = wrapped_row_indices(len(idx), axis_size)
            if wrap is not None:
                idx = idx[wrap]
        batch = {k: v[idx] for k, v in host.items()}
        if sharding is not None:
            batch = {k: jax.device_put(v, sharding) for k, v in batch.items()}
        yield batch


def fit(
    state: TrainState,
    data: Dict[str, np.ndarray],
    *,
    batch_size: int,
    num_epochs: int = 1,
    num_steps: Optional[int] = None,
    mesh: Optional[Mesh] = None,
    param_spec: Any = None,
    input_signature: Tuple[str, ...] = ("inputs",),
    checkpoint_dir: Optional[str] = None,
    checkpoint_every: int = 100,
    log_every: int = 50,
    seed: int = 0,
    prefetch: bool = False,
    prefetch_convert: Optional[Dict[str, str]] = None,
    step_fn: Optional[Callable] = None,
    grad_accum: int = 1,
) -> FitResult:
    """Run the compiled train loop; resumes from ``checkpoint_dir`` when present.

    ``step_fn`` overrides the default classifier step with any compiled
    ``(state, batch) -> (state, metrics)`` — :func:`make_lm_train_step` and
    :func:`fit_lm` route packed-LM training through here, so every loop feature
    (checkpointing, prefetch, mesh batch layout, timing) is shared.

    ``prefetch=True`` gathers batches with the native threaded prefetcher
    (:class:`unionml_tpu.native.PrefetchLoader`), overlapping host-side batch assembly
    with device compute; falls back to Python batching when the native build is
    unavailable. ``prefetch_convert`` (e.g. ``{"inputs": "float32", "labels":
    "int32"}`` for raw pandas f64/i64 data, or ``{"inputs": "bfloat16"}`` for
    float32 sources) runs the per-array dtype conversion inside the native worker
    threads during the gather, so host data reaches the device in its compute
    dtype without Python ever paying element-wise conversion. Requires
    ``prefetch=True`` — silently skipping a requested conversion would be a
    correctness trap.
    """
    if step_fn is not None and grad_accum != 1:
        # silently ignoring a requested option is a correctness trap (same
        # stance as prefetch_convert below): accumulation belongs to the step
        # builder, so pass grad_accum to make_*_train_step instead
        raise ValueError("grad_accum applies to the built-in step; pass it to your step builder")
    if prefetch_convert and not prefetch:
        raise ValueError("prefetch_convert requires prefetch=True (conversion runs in the native gather workers)")
    # the arguments hold: from here the call is on its timeline
    timeline = PhaseTimeline("fit", FIT_PHASES)
    timeline.enter("start")
    configure_compile_cache()
    if step_fn is None:
        step_fn = make_classifier_train_step(
            mesh=mesh, param_spec=param_spec, input_signature=input_signature,
            grad_accum=grad_accum,
        )

    prefetch_loader = None
    if prefetch:
        from unionml_tpu.native import PrefetchLoader

        prefetch_loader = PrefetchLoader(data, batch_size, convert=prefetch_convert)

    def batch_iterator(epoch_rng):
        if prefetch_loader is not None:
            sharding = batch_sharding(mesh) if mesh is not None else None
            axis = batch_axis_size(mesh) if mesh is not None else 1
            # copy=False feeds the loader's python-owned slot buffers straight to
            # device_put (zero host copies after the native gather) — safe ONLY for
            # real accelerators, where the transfer lands in separate device memory
            # and block_until_ready fences it. The CPU backend may ALIAS an
            # aligned host array instead of copying, so slot recycling would
            # corrupt "transferred" batches — keep the host copy there.
            zero_copy = jax.default_backend() != "cpu"

            def transfers():
                # deferred slot release lets batch N+1's host->device transfer fly
                # while step N computes: the slot recycles only after
                # block_until_ready proves its transfer landed
                for views, release in prefetch_loader.epoch(
                    rng=epoch_rng, copy=not zero_copy, defer_release=True
                ):
                    if sharding is not None:
                        n = len(next(iter(views.values())))
                        wrap = wrapped_row_indices(n, axis)
                        if wrap is not None:  # ragged tail: wrap real rows to fit the mesh
                            views = {k: v[wrap] for k, v in views.items()}
                        yield {k: jax.device_put(v, sharding) for k, v in views.items()}, release
                    else:
                        yield {k: jax.device_put(v) for k, v in views.items()}, release

            pending = None
            for batch_and_release in transfers():
                if pending is not None:
                    batch, release = pending
                    jax.block_until_ready(batch)
                    release()
                    yield batch
                pending = batch_and_release
            if pending is not None:
                batch, release = pending
                jax.block_until_ready(batch)
                release()
                yield batch
            return
        yield from dict_batches(data, batch_size, rng=epoch_rng, mesh=mesh)

    checkpointer = None
    if checkpoint_dir is not None:
        from unionml_tpu.checkpoint import Checkpointer, install_preemption_handler

        checkpointer = Checkpointer(checkpoint_dir, save_interval_steps=checkpoint_every)
        install_preemption_handler(checkpointer)
        latest = checkpointer.latest_step()
        if latest is not None:
            logger.info("Resuming from checkpoint step %d", latest)
            state = checkpointer.restore(state)

    rng = np.random.default_rng(seed)
    history = []
    step = int(state.step)
    start_step = step
    # compile outside the timed region so wall-clock measures steady-state steps
    first_batch = next(iter(batch_iterator(rng)))
    state, metrics = step_fn(state, first_batch)
    jax.block_until_ready(metrics)
    step += 1

    # the loop is in input_wait whenever the iterator runs (the for statement's
    # own next() included), and in dispatch, log or checkpoint inside the body
    t0 = timeline.enter("input_wait")
    done = False
    # an explicit step budget overrides the epoch count (loops data as needed)
    epochs = num_epochs if num_steps is None else max(num_epochs, 10**9)
    for epoch in range(epochs):
        for batch in batch_iterator(rng):
            timeline.enter("dispatch")
            state, metrics = step_fn(state, batch)
            step += 1
            if step % log_every == 0:
                timeline.enter("log")
                metrics_host = {k: float(v) for k, v in metrics.items()}
                history.append({"step": step, **metrics_host})
                logger.info("step %d: %s", step, metrics_host)
            if checkpointer is not None:
                timeline.enter("checkpoint")
                checkpointer.save(step, state)
            if num_steps is not None and step - start_step >= num_steps:
                done = True
                break
            timeline.enter("input_wait")
        if done:
            break
    timeline.enter("drain")
    jax.block_until_ready(metrics)  # the timed region ends when the last step has run
    wall = timeline.enter("finish") - t0
    if checkpointer is not None:
        checkpointer.flush()
    if prefetch_loader is not None:
        prefetch_loader.close()
    timeline.leave()

    executed = step - start_step - 1  # first (compile) step excluded from the timing
    phase_seconds = {phase: totals["seconds"] for phase, totals in timeline.snapshot().items()}
    logger.info(
        "fit: %d steps in %.3f s after the first; seconds by phase: %s", executed, wall,
        ", ".join(f"{phase} {seconds:.3f}" for phase, seconds in phase_seconds.items()),
    )
    return FitResult(
        state=state,
        metrics_history=history,
        steps=step,
        wall_time_s=wall,
        steps_per_s=executed / wall if wall > 0 else 0.0,
        examples_per_s=executed * batch_size / wall if wall > 0 else 0.0,
        phase_seconds=phase_seconds,
    )


def fit_lm(
    state: TrainState,
    sequences: Sequence[np.ndarray],
    *,
    seq_len: int,
    batch_size: int,
    pack: bool = True,
    max_segments_per_row: int = 0,
    num_epochs: int = 1,
    num_steps: Optional[int] = None,
    mesh: Optional[Mesh] = None,
    param_spec: Any = None,
    checkpoint_dir: Optional[str] = None,
    checkpoint_every: int = 100,
    log_every: int = 50,
    seed: int = 0,
    prefetch: bool = False,
    prefetch_convert: Optional[Dict[str, str]] = None,
    grad_accum: int = 1,
    moe_aux: bool = False,
) -> FitResult:
    """Causal-LM training over RAGGED token sequences through the shared fit loop.

    ``pack=True`` (the default) runs
    :func:`unionml_tpu.ops.packing.pack_sequences`: several short sequences share
    each fixed-shape row, segment ids confine attention and restart positions per
    segment, and cross-segment next-token transitions are masked out of the loss —
    so a packed batch trains exactly as its sequences would alone while wasting no
    MXU cycles on padding. ``pack=False`` right-pads one sequence per row with a
    loss mask (the naive layout, kept for ablations).

    This is the public packed-training entrypoint the reference cannot express at
    all: its training loop is opaque user code (reference ``unionml/model.py:560``
    runs the trainer inline), with no packing support anywhere.
    """
    from unionml_tpu.ops.packing import pack_sequences, packing_efficiency

    if pack:
        packed = pack_sequences(sequences, seq_len, max_segments_per_row=max_segments_per_row)
        data = {"input_ids": packed["input_ids"], "segment_ids": packed["segment_ids"]}
        logger.info(
            "packed %d sequences into %d rows of %d (efficiency %.1f%%, %d truncated)",
            len(sequences),
            packed["input_ids"].shape[0],
            seq_len,
            100.0 * packing_efficiency(packed["segment_ids"]),
            packed["truncated"],
        )
    else:
        input_ids = np.zeros((len(sequences), seq_len), dtype=np.int32)
        mask = np.zeros((len(sequences), seq_len), dtype=np.float32)
        truncated = 0
        for i, seq in enumerate(sequences):
            arr = np.asarray(seq).reshape(-1)[:seq_len]
            truncated += int(np.asarray(seq).size > seq_len)
            input_ids[i, : arr.size] = arr
            mask[i, : arr.size] = 1.0
        if truncated:
            logger.info("truncated %d sequences to seq_len=%d", truncated, seq_len)
        data = {"input_ids": input_ids, "mask": mask}

    step_fn = make_lm_train_step(
        mesh=mesh, param_spec=param_spec, packed=pack, grad_accum=grad_accum, moe_aux=moe_aux
    )
    return fit(
        state,
        data,
        batch_size=batch_size,
        num_epochs=num_epochs,
        num_steps=num_steps,
        mesh=mesh,
        param_spec=param_spec,
        checkpoint_dir=checkpoint_dir,
        checkpoint_every=checkpoint_every,
        log_every=log_every,
        seed=seed,
        prefetch=prefetch,
        prefetch_convert=prefetch_convert,
        step_fn=step_fn,
    )


def bert_flops_per_token(config: Any) -> float:
    """Approximate training FLOPs per token for MFU accounting (6 * params-ish)."""
    hidden, layers, inter = config.hidden_size, config.num_layers, config.intermediate_size
    per_layer = 4 * hidden * hidden + 2 * hidden * inter  # attn projections + mlp
    embed = 0  # lookup, negligible FLOPs
    fwd = layers * 2 * per_layer + embed  # 2 flops per MAC
    return 3.0 * fwd  # fwd + bwd ~ 3x forward

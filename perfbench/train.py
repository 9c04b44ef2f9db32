"""The training driver: ``models.training.fit`` with the packed LM step, the
loop ``fit_lm`` routes through, in successive calls with the state carried over.

Set-up builds one compiled step and one train state, drives them through their
first steps with the window's own call and feed (which compiles the step and
gives the check its readings), calibrates how many steps make a call of 2-3 s,
and packs the window's documents. The window then calls ``fit`` until
``--seconds`` have passed; the last call ends with ``block_until_ready``, and
the rate is taken over all tokens and all the time up to there. What depends on
the model's architecture is asked of the configuration's family
(``cell.family()``) and of its reference (``cell.reference()``).
"""

from __future__ import annotations

import functools
import gc
import statistics
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import common, costs, traffic, weights

FOLLOWED_STEPS = 3  # the reference follows the program through this many steps


@functools.partial(jax.jit, static_argnames=("leaves",))
def leaf_norms(tree: Any, leaves: Callable[[Any], Any]) -> Any:
    """The norm of each of the architecture's leaves: ``leaves`` is the family's
    ``architecture_leaves``, which splits what the program keeps fused."""
    return jax.tree.map(lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), leaves(tree))


@functools.partial(jax.jit, static_argnames=("leaves",))
def delta_norms(after: Any, before: Any, leaves: Callable[[Any], Any]) -> Any:
    return leaf_norms(
        jax.tree.map(lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32), after, before), leaves
    )


def adam_state(opt_state: Any) -> Any:
    """The optimizer state's Adam part: the node that has ``mu`` and ``nu``."""
    found = [
        node for node in jax.tree.leaves(opt_state, is_leaf=lambda n: hasattr(n, "mu") and hasattr(n, "nu"))
        if hasattr(node, "mu") and hasattr(node, "nu")
    ]
    if len(found) != 1:
        raise RuntimeError(f"expected one Adam state in the optimizer state, found {len(found)}")
    return found[0]


class StepRecorder:
    """Stands between ``fit`` and the compiled step. It holds on to what each
    step was fed (device arrays ``fit`` made anyway; nothing is computed per
    step), and for the first steps to the losses, to the first moment after
    step 1 and to the parameters' change after step 3.

    ``fault`` plants one of the faults the check has to catch, in the feed or
    underneath the step: ``half_batch`` (the second half of the rows left out,
    the mean taken over the rest) or ``state_unchanged``.
    """

    def __init__(self, step: Callable, initial_params: Callable[[], Any],
                 leaves: Callable[[Any], Any], fault: Optional[str] = None) -> None:
        self._step = step
        self._initial_params = initial_params
        self._leaves = leaves
        self.fault = fault
        self.calls = 0
        self.fed: List[Dict[str, Any]] = []  # first steps: the whole batch
        self.segments: List[Any] = []  # every later step: its segment ids
        self.losses: List[Any] = []
        self.first_moment_norms: Any = None
        self.delta_norms: Any = None
        self.last_metrics: Any = None

    def __call__(self, state: Any, batch: Dict[str, Any]):
        self.calls += 1
        if self.calls <= FOLLOWED_STEPS:
            self.fed.append(dict(batch))
        else:
            self.segments.append(batch["segment_ids"])
        fed = batch
        if self.fault == "half_batch":
            rows = batch["segment_ids"].shape[0]
            fed = {**batch, "segment_ids": batch["segment_ids"].at[rows // 2 :].set(0)}
        if self.fault == "state_unchanged":
            kept = jax.tree.map(jnp.copy, state)
            _, metrics = self._step(state, fed)
            state = kept
        else:
            state, metrics = self._step(state, fed)
        if self.calls <= FOLLOWED_STEPS:
            self.losses.append(metrics["loss"])
        if self.calls == 1:
            self.first_moment_norms = leaf_norms(adam_state(state.opt_state).mu, self._leaves)
        if self.calls == FOLLOWED_STEPS:
            self.delta_norms = delta_norms(state.params, self._initial_params(), self._leaves)
        self.last_metrics = metrics
        return state, metrics

    def reset_window(self) -> None:
        self.segments = []


def pack_rows(docs: List[np.ndarray], seq_len: int) -> Dict[str, np.ndarray]:
    from unionml_tpu.ops.packing import pack_sequences

    packed = pack_sequences(docs, seq_len)
    return {"input_ids": packed["input_ids"], "segment_ids": packed["segment_ids"]}


def rows_for(mix: Dict[str, Any], seed: int, vocab: int, seq_len: int, rows: int, salt: int):
    """At least ``rows`` packed rows of fresh documents (their own stream of the seed)."""
    mean_len = float(np.mean(traffic.quantile_set(mix["document_tokens"], int(mix["pool"]))))
    count = int(rows * seq_len / mean_len * 1.08) + 8
    while True:
        docs = traffic.documents(mix, seed * 1000003 + salt, vocab, count)
        packed = pack_rows(docs, seq_len)
        if packed["input_ids"].shape[0] >= rows:
            return {k: v[:rows] for k, v in packed.items()}
        count = int(count * 1.2) + 8


def document_lengths(segment_ids: np.ndarray) -> List[int]:
    lengths = []
    for row in segment_ids:
        row = row[row > 0]
        if row.size:
            lengths.extend(np.bincount(row)[1:].tolist())
    return [n for n in lengths if n > 0]


# --------------------------------------------------------------------- check


def _gap(program: Dict[str, float], ref: Dict[str, float], keep: Dict[str, bool]) -> float:
    """Worst leaf: the gap between the program's norm and the reference's,
    against the reference's norm of that leaf or of the median leaf, whichever
    is larger."""
    median = statistics.median(ref.values())
    return max(
        abs(program[k] - ref[k]) / max(ref[k], median) for k in ref if keep.get(k, True)
    )


def _flat(tree: Any) -> Dict[str, float]:
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(path): float(value) for path, value in leaves}


def follow(cell, params: Any, batches: List[Dict[str, Any]],
           lowp: Optional[str] = None, half_batch: bool = False) -> Dict[str, Any]:
    """The cell's reference through the first steps: losses, the clipped first
    gradient's norms by leaf and the norms of the parameters' change."""
    reference, family = cell.reference(), cell.family()
    trainer = cell.config["perfbench"]["trainer"]
    kw = family.reference_kwargs(cell.config)
    leaves = family.architecture_leaves
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    start = params
    losses, first_grad = [], None
    for k, batch in enumerate(batches, start=1):
        segments = jnp.asarray(batch["segment_ids"])
        if half_batch:
            segments = segments.at[segments.shape[0] // 2 :].set(0)
        loss, grads = reference.loss_and_grads(
            params, jnp.asarray(batch["input_ids"]), segments, lowp=lowp, **kw
        )
        params, mu, nu, clipped = reference.adamw_step(
            params, mu, nu, grads, jnp.asarray(k), jnp.float32(trainer["learning_rate"]),
            weight_decay=trainer["weight_decay"], max_grad_norm=trainer["max_grad_norm"],
        )
        losses.append(float(loss))
        if k == 1:
            first_grad = _flat(leaf_norms(clipped, leaves))
    return {"losses": losses, "grad_norms": first_grad,
            "delta_norms": _flat(delta_norms(params, start, leaves))}


def compare(program: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, float]:
    median_grad = statistics.median(ref["grad_norms"].values())
    # a leaf whose gradient is nought to rounding in the reference moves under
    # Adam by round-off alone: it is left out of the change, by this rule
    moved = {k: g >= 1e-3 * median_grad for k, g in ref["grad_norms"].items()}
    numbers = {
        f"loss{k}_gap": abs(p - r) / abs(r)
        for k, (p, r) in enumerate(zip(program["losses"], ref["losses"]), start=1)
    }
    numbers["grad_norm_gap"] = _gap(program["grad_norms"], ref["grad_norms"], {})
    numbers["delta_norm_gap"] = _gap(program["delta_norms"], ref["delta_norms"], moved)
    return numbers


def check(recorder: StepRecorder, cell, seed: int, control: bool) -> Dict[str, Any]:
    limits = cell.limits
    b1 = 0.9
    program = {
        "losses": [float(x) for x in recorder.losses],
        "grad_norms": {k: v / (1 - b1) for k, v in _flat(recorder.first_moment_norms).items()},
        "delta_norms": _flat(recorder.delta_norms),
    }
    params = cell.family().make_params(cell.config, seed, "float32")
    batches = [{k: np.asarray(v) for k, v in b.items()} for b in recorder.fed]
    ref = follow(cell, params, batches)
    numbers = {k: [v, limits.get(k)] for k, v in compare(program, ref).items()}
    correct = all(limit is None or value <= limit for value, limit in numbers.values())
    correct = correct and all(np.isfinite(value) for value, _ in numbers.values())
    if control:
        for mode in limits["controls"]:
            low = compare(follow(cell, params, batches, lowp=mode), ref)
            numbers.update({f"control_{mode}_{k}": [v, None] for k, v in low.items()})
        half = compare(follow(cell, params, batches, half_batch=True), ref)
        numbers.update({f"halfbatch_{k}": [v, None] for k, v in half.items()})
    return {"correct": bool(correct), "numbers": numbers}


# ----------------------------------------------------------------------- run


def first_steps(cell, seed: int, step: Optional[Callable] = None, fault: Optional[str] = None):
    """What set-up builds and the window then drives: the train state, the
    compiled step behind its recorder, and the call into ``fit``; driven from the
    seed through the first steps, the ones the reference follows."""
    from unionml_tpu.models import create_train_state
    from unionml_tpu.models.training import fit, make_lm_train_step

    config, mix, family = cell.config, cell.mix, cell.family()
    trainer = config["perfbench"]["trainer"]
    rows, seq_len, vocab = int(trainer["rows_per_step"]), int(trainer["seq_len"]), config["vocab_size"]

    def initial_params():
        return family.make_params(config, seed, "float32")

    state = create_train_state(
        family.model(config), {"params": initial_params()}, learning_rate=trainer["learning_rate"],
        weight_decay=trainer["weight_decay"], warmup_steps=0,
        max_grad_norm=trainer["max_grad_norm"], rng=weights.seed_key(seed, 1),
    )
    recorder = StepRecorder(step or make_lm_train_step(packed=True), initial_params,
                            family.architecture_leaves, fault=fault)

    def call_fit(state, data):
        return fit(state, data, batch_size=rows, num_epochs=1, prefetch=True,
                   seed=seed % (2**31), step_fn=recorder).state

    # fit runs one batch, then an epoch, so two batches of rows make three
    # steps; the first of them compiles the step
    state = call_fit(state, rows_for(mix, seed, vocab, seq_len, 2 * rows, salt=1))
    jax.block_until_ready(state.params)
    return state, recorder, call_fit


def run(cell, args, t0: float) -> Dict[str, Any]:
    from unionml_tpu.utils import configure_compile_cache

    configure_compile_cache()
    counter = common.CompileCounter()
    phases = common.Phases(t0, counter)
    phases.mark("imported")
    config, mix = cell.config, cell.mix
    trainer = config["perfbench"]["trainer"]
    rows, seq_len, vocab = int(trainer["rows_per_step"]), int(trainer["seq_len"]), config["vocab_size"]
    state, recorder, call_fit = first_steps(cell, args.seed, fault=getattr(args, "fault", None))
    phases.mark("first_steps")
    # calibration: a call of eight batches, timed, sets the steps of a call
    before = time.perf_counter()
    state = call_fit(state, rows_for(mix, args.seed, vocab, seq_len, 8 * rows, salt=2))
    step_s = (time.perf_counter() - before) / 9
    phases.mark("calibrated")
    call_s = float(mix.get("call_seconds", 2.5))
    batches_per_call = max(2, int(round(call_s / step_s)) - 1)
    calls = int(args.seconds / (batches_per_call * step_s) * 1.3) + 3
    data = rows_for(mix, args.seed, vocab, seq_len, calls * batches_per_call * rows, salt=3)
    chunks = [
        {k: v[i * batches_per_call * rows : (i + 1) * batches_per_call * rows] for k, v in data.items()}
        for i in range(calls)
    ]

    phases.mark("packed")
    tracer = common.Tracer() if args.trace else None
    recorder.reset_window()
    misses_open = counter.misses
    if tracer is not None:
        tracer.start()
    t_open = time.perf_counter()
    used = 0
    while True:
        state = call_fit(state, chunks[used % len(chunks)])
        used += 1
        elapsed = time.perf_counter() - t_open
        if tracer is not None and elapsed >= float(mix.get("trace_seconds", 2.0)):
            tracer.stop()
        if elapsed >= args.seconds:
            break
    compiles = counter.misses - misses_open
    phases.mark("window_closed")
    if tracer is not None:
        tracer.finish()
        phases.mark("trace_read")

    steps = len(recorder.segments)
    tokens = int(sum(int(jnp.sum(s > 0)) for s in recorder.segments))
    last_loss = float(recorder.last_metrics["loss"])
    memory_peak = common.memory_peak_bytes()
    consumed = np.concatenate([chunks[i % len(chunks)]["segment_ids"] for i in range(used)])
    fed = {
        "steps": steps, "tokens": tokens, "slots": steps * rows * seq_len,
        "mean_keys": costs.mean_causal_keys(document_lengths(consumed)),
        "calls": used, "batches_per_call": batches_per_call, "repeated_data": used > len(chunks),
    }
    e2e = {"train_tokens_per_s": tokens / elapsed, "setup_s": t_open - t0}

    del state
    recorder._step = None
    gc.collect()
    checked = check(recorder, cell, args.seed, control=bool(args.control))
    phases.mark("checked")
    context = {
        "cell": cell, "config": config, "family": cell.family(), "mix": mix, "fed": fed, "e2e": e2e,
        "trace": tracer.summary if tracer is not None else None,
        "trace_interval": tracer.interval if tracer is not None else None,
        "compiles_in_window": compiles, "phases": phases.marks,
    }
    return {
        "context": context, "e2e": e2e, "checked": checked, "memory_peak_bytes": memory_peak,
        "attempted": steps, "failed": 0 if np.isfinite(last_loss) else steps,
    }

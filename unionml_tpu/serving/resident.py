"""Resident predictor: a pre-compiled XLA executable serving online predictions.

Reference behavior: the FastAPI path routes every request through
``model.predict(features=...)`` interpreted Python (``unionml/fastapi.py:50-64``). The
TPU-native rebuild pre-lowers and compiles the predictor at server startup for a ladder
of padded batch shapes ("bucketing"), so the request path is: host->device transfer,
run resident executable, device->host — the p50-latency metric.

Dynamic request sizes vs XLA static shapes (SURVEY.md §7 "hard parts"): request batches
pad up to the nearest bucket; predictions slice back down. Two bucketing axes:

- **batch** (dim 0, always on): requests pad up the ``buckets`` ladder.
- **sequence** (dim 1, opt-in via ``seq_buckets``): tokenized inputs (BERT-style
  ``input_ids``/``attention_mask`` dicts) pad their sequence dimension up a second
  ladder, so a 37-token request reuses the 64-token executable instead of compiling
  a fresh shape per length.

Features may be a single array OR a dict/pytree of arrays sharing a leading batch dim
(multi-input models). Opaque model objects (sklearn/torch) bypass compilation and run
eagerly — same endpoint, same semantics.

Warmup sources, in priority order: an explicit ``example_features`` request payload
(rows exactly as a client would POST them — covers tokenized/multi-input models), else
the dataset's flat feature metadata. Pass ``example_features`` through
``model.serve(example_features=[...])``.
"""

import threading
import time
from collections import deque
from typing import Any, Optional, Sequence, Tuple

import jax
import numpy as np

from unionml_tpu._logging import logger
from unionml_tpu.stage import is_jax_compatible

DEFAULT_BUCKETS: Tuple[int, ...] = (1, 2, 4, 8, 16, 32, 64, 128, 256)


def _ladder_value(ladder: Tuple[int, ...], n: int) -> int:
    """Smallest ladder entry >= n; oversize rounds up to a multiple of the largest."""
    for rung in ladder:
        if rung >= n:
            return rung
    largest = ladder[-1]
    return ((n + largest - 1) // largest) * largest


class ResidentPredictor:
    """Holds a model artifact on-device with a compiled predict executable."""

    def __init__(
        self,
        model: Any,
        buckets: Sequence[int] = DEFAULT_BUCKETS,
        warmup: bool = True,
        seq_buckets: Optional[Sequence[int]] = None,
        example_features: Optional[Any] = None,
        mesh: Optional[Any] = None,
        param_specs: Optional[Any] = None,
    ):
        """``mesh`` (a ``jax.sharding.Mesh``) serves the compiled predictor across
        every mesh device: the model artifact commits to the mesh once at setup —
        laid out by ``param_specs`` (a ``PartitionSpec`` pytree matching the model
        object, e.g. a family's ``param_shardings`` table) or replicated when
        ``None`` — and request batches shard their leading dim over the ``data``
        axis when the padded bucket divides. Outputs are identical to the
        single-device predictor; only the layout changes."""
        self._model = model
        self._buckets = tuple(sorted(buckets))
        self._seq_buckets = tuple(sorted(seq_buckets)) if seq_buckets else None
        self._example_features = example_features
        self._mesh = mesh
        self._param_specs = param_specs
        self._warmup = warmup
        self._compiled = None
        self._device_model_object = None
        # serializes setup(): predict() runs on executor threads, and several
        # first requests can race into the lazy init — exactly one may compile
        # and commit the artifact to device (the rest wait, then see _ready)
        self._setup_lock = threading.Lock()
        self._ready = False  # guarded-by: _setup_lock
        # per-request device-side latency (dispatch + device->host fetch), ms —
        # the server-side half of the device/HTTP latency split (VERDICT r3 #8):
        # /stats quotes these so client/network RTT never masquerades as model time.
        # predict() appends from executor threads while /stats reads on the event
        # loop; the lock keeps the snapshot safe (deques error on mutation mid-iter)
        self._device_times_ms: deque = deque(maxlen=2048)
        self._device_times_lock = threading.Lock()
        # shape signatures whose executable has already run once: the FIRST call
        # at a new padded shape pays trace+compile, which must not be recorded as
        # steady-state device latency (it would sit in the window as a bogus p99)
        self._timed_shapes: set = set()

    def device_stats(self) -> dict:
        """Percentiles of the compiled executable's per-request wall time."""
        with self._device_times_lock:
            times = sorted(self._device_times_ms)
        if not times:
            return {"count": 0}
        at = lambda q: round(times[min(int(len(times) * q), len(times) - 1)], 3)
        return {
            "count": len(times),
            "device_p50_ms": at(0.50),
            "device_p90_ms": at(0.90),
            "device_p99_ms": at(0.99),
        }

    def setup(self) -> None:
        """Decide the execution mode and (if traceable) compile + warm the predictor.

        Idempotent and thread-safe: concurrent first requests race through
        predict()'s fast-path readiness check, so the body runs under
        ``_setup_lock`` and re-checks — exactly one caller compiles and
        commits the artifact to device; the rest block until it is ready."""
        with self._setup_lock:
            if self._ready:
                return
            artifact = self._model.artifact
            if artifact is None:
                raise RuntimeError("ResidentPredictor.setup requires a loaded model artifact.")

            predictor = self._model._predictor
            model_object = artifact.model_object
            if is_jax_compatible(model_object):
                predictor_fn = getattr(predictor, "fn", predictor)
                if self._mesh is not None:
                    # mesh-resident artifact: parameters commit to every mesh device
                    # once (sharded per param_specs, else replicated); the compiled
                    # predictor then runs tensor/data-parallel across the mesh
                    from unionml_tpu.parallel.mesh import named_sharding_tree, replicated

                    shardings = (
                        named_sharding_tree(self._mesh, self._param_specs)
                        if self._param_specs is not None
                        else replicated(self._mesh)
                    )
                    self._device_model_object = jax.device_put(model_object, shardings)  # graftlint: disable=data-race -- published once under _setup_lock; readers run only after the _ready check, which happens-after this write
                else:
                    # keep the artifact resident on device: no host->device transfer per request
                    self._device_model_object = jax.tree_util.tree_map(jax.numpy.asarray, model_object)  # graftlint: disable=data-race -- published once under _setup_lock; readers run only after the _ready check, which happens-after this write
                self._compiled = jax.jit(predictor_fn)  # graftlint: disable=data-race -- published once under _setup_lock; readers run only after the _ready check, which happens-after this write
                if self._warmup:
                    self._warm()  # graftlint: disable=lock-order -- one-time init: racing first requests MUST wait for compile+warm before serving, so blocking under _setup_lock is the contract
            else:
                logger.info("Model object is not a jax pytree; serving will run the predictor eagerly.")
            self._ready = True

    def _warm(self) -> None:
        """Compile the smallest bucket ahead of the first request."""
        try:
            example = self._example_processed(self._buckets[0])
            if example is None:
                logger.info(
                    "No warmup template (pass example_features to serve()); first request will compile."
                )
                return
            jax.block_until_ready(self._compiled(self._device_model_object, example))
            logger.info("Resident predictor warmed (bucket=%d).", self._buckets[0])
        except Exception as exc:
            # keep the compiled predictor: the synthetic example may simply have the
            # wrong dtype/shape for this model; the first real request still compiles
            logger.info("Warmup skipped (%s: %s); first request will compile.", type(exc).__name__, exc)

    def _example_processed(self, batch: int) -> Optional[Any]:
        """A processed, bucket-shaped feature pytree for warmup compilation.

        Priority: run the user-supplied ``example_features`` request rows through the
        real feature pipeline and pad them exactly like a live request (covers
        multi-input/tokenized models), else synthesize zero features from flat
        feature-column metadata.
        """
        if self._example_features is not None:
            example = self._example_features
            if isinstance(example, list) and example:
                # resize the example rows to the requested bucket so warmup compiles
                # the executable real requests will actually hit (smallest bucket)
                example = [example[i % len(example)] for i in range(batch)]
            processed = self._model.dataset.get_features(example)
            padded, _, _ = self._pad_to_buckets(processed)
            return padded
        feature_columns = getattr(self._model.dataset, "_features", None)
        if feature_columns:
            return jax.numpy.zeros((batch, len(feature_columns)), dtype=jax.numpy.float32)
        return None

    def _bucket_for(self, n: int) -> int:
        return _ladder_value(self._buckets, n)

    # ------------------------------------------------------------------ padding

    def _array_leaves(self, processed: Any):
        """Flatten processed features; returns (leaves, treedef) or None if any leaf
        is not a batch-dim array (opaque features run eagerly)."""
        leaves, treedef = jax.tree_util.tree_flatten(processed)
        if not leaves:
            return None
        arrays = []
        for leaf in leaves:
            if not is_jax_compatible(leaf) or not hasattr(leaf, "shape") or getattr(leaf, "ndim", 0) < 1:
                return None
            arrays.append(leaf)
        n = arrays[0].shape[0]
        if any(a.shape[0] != n for a in arrays):
            return None
        return arrays, treedef, n

    def _pad_to_buckets(self, processed: Any):
        """Pad every array leaf's batch dim (and sequence dim, when configured) up the
        bucket ladders. Returns (padded_pytree, original_batch, batch_bucket).

        Sequence-dim padding applies only to DICT (multi-input/tokenized) features: a
        single flat feature MATRIX — even an integer one (ordinal/categorical
        encodings) — has a fixed width that must never grow fabricated columns."""
        is_multi_input = isinstance(processed, dict)
        flat = self._array_leaves(processed)
        if flat is None:
            raise ValueError("features are not a batch-dim array pytree")
        arrays, treedef, n = flat
        bucket = self._bucket_for(n)
        padded = []
        for a in arrays:
            a = np.asarray(a) if not isinstance(a, jax.Array) else a
            if a.dtype == np.float64:
                a = a.astype(np.float32)
            pad = [(0, 0)] * a.ndim
            if bucket != n:
                pad[0] = (0, bucket - n)
            # dim 1 is a sequence axis for integer leaves (token ids / masks) and
            # rank>=3 leaves (batch, seq, features); a rank-2 FLOAT leaf is a flat
            # feature matrix whose width must never be padded (a dense (b, 10)
            # input would otherwise grow fabricated zero columns)
            is_seq_leaf = np.issubdtype(a.dtype, np.integer) or a.ndim >= 3
            if self._seq_buckets is not None and a.ndim >= 2 and is_seq_leaf and is_multi_input:
                seq = a.shape[1]
                seq_bucket = _ladder_value(self._seq_buckets, seq)
                if seq_bucket != seq:
                    pad[1] = (0, seq_bucket - seq)
            if any(p != (0, 0) for p in pad):
                a = np.pad(np.asarray(a), pad)
            padded.append(self._to_device(a, bucket))
        return jax.tree_util.tree_unflatten(treedef, padded), n, bucket

    def _to_device(self, leaf: Any, bucket: int) -> Any:
        """Place one padded leaf: batch-sharded over the mesh's data axis when the
        bucket divides evenly (per-row work fans out), replicated otherwise;
        plain single-device transfer without a mesh."""
        if self._mesh is None:
            return jax.numpy.asarray(leaf)
        from unionml_tpu.parallel.mesh import batch_axis_size, batch_sharding, replicated

        n_shards = batch_axis_size(self._mesh)
        sharding = (
            batch_sharding(self._mesh)
            if n_shards > 1 and bucket % n_shards == 0
            else replicated(self._mesh)
        )
        return jax.device_put(leaf, sharding)

    # ------------------------------------------------------------------ request path

    def predict(self, features: Any = None, **reader_kwargs) -> Any:
        """Request-path prediction; uses the resident executable when possible."""
        if not self._ready:  # graftlint: disable=data-race -- benign double-checked fast path; setup() re-checks under _setup_lock before doing any work
            self.setup()
        if self._compiled is None or features is None:
            return self._model.predict(features=features, **reader_kwargs)

        processed = self._model.dataset.get_features(features)
        try:
            padded, n, bucket = self._pad_to_buckets(processed)
        except ValueError:
            return self._model.predict(features=features, **reader_kwargs)

        shape_sig = tuple(
            (getattr(leaf, "shape", None), str(getattr(leaf, "dtype", "")))
            for leaf in jax.tree_util.tree_leaves(padded)
        )
        # warm status is snapshotted BEFORE dispatch: a request that starts while
        # another request is still paying this shape's trace+compile waits on that
        # same compile, so only requests that started after the shape was marked
        # warm (at a prior call's completion) may record a steady-state sample
        with self._device_times_lock:
            was_warm = shape_sig in self._timed_shapes
        t0 = time.perf_counter()
        try:
            predictions = self._compiled(self._device_model_object, padded)
        except Exception as exc:
            logger.info("Resident predict failed (%s); falling back to eager predict.", exc)
            self._compiled = None
            return self._model.predict(features=features, **reader_kwargs)
        predictions = jax.device_get(predictions)  # the fetch is the device barrier
        elapsed_ms = (time.perf_counter() - t0) * 1e3
        with self._device_times_lock:
            if was_warm:
                self._device_times_ms.append(elapsed_ms)
            else:  # this call (and any concurrent peer) paid trace+compile: never record it
                self._timed_shapes.add(shape_sig)
        # slice the padding off every batch-shaped leaf (predictor outputs may be pytrees)
        result = jax.tree_util.tree_map(
            lambda leaf: leaf[:n]
            if hasattr(leaf, "shape") and leaf.ndim >= 1 and leaf.shape[0] == bucket
            else leaf,
            predictions,
        )
        self._model._run_predict_callbacks(self._device_model_object, processed, result)
        return result

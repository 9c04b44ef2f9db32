"""Regression tests for the round-1 advisor findings (ADVICE.md) and VERDICT weak #7.

Each test pins one specific fixed behavior:
- stage.py: a single trace failure must not permanently downgrade a TracedFunction
- ring.py: fully-padded query rows must emit zeros, not garbage V sums
- schedule.py: cron 'N/step' expands as a range start (croniter semantics)
- dp.py / training.py: ragged batches pad up to the mesh data axis before device_put
- model.py: ad-hoc hyperparameter dicts must not mutate shared Model state
"""

import threading
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd
import pytest

from unionml_tpu import Dataset, Model
from unionml_tpu.ops.attention import xla_attention
from unionml_tpu.parallel import batches, make_mesh
from unionml_tpu.parallel.ring import ring_attention, sequence_sharding
from unionml_tpu.schedule import CronSpec, parse_cron
from unionml_tpu.stage import TracedFunction


# ---------------------------------------------------------------- stage.py latch

def test_trace_failure_does_not_permanently_downgrade():
    """ADVICE #1: one bad call shape falls back eagerly; other shapes stay jitted."""

    def f(x, mode="fast"):
        if mode == "concrete":
            # data-dependent Python branch: fails under trace, fine eagerly
            if x[0] > 0:
                return x
            return -x
        return x * 2

    tf = TracedFunction(f, jit="auto")
    x = jnp.asarray([1.0, 2.0])

    # the failing structure falls back for that call...
    np.testing.assert_allclose(np.asarray(tf(x, mode="concrete")), np.asarray(x))
    # ...but the instance is NOT latched eager
    assert tf.uses_jit
    # a different static VALUE of the same kwarg still compiles and runs jitted
    np.testing.assert_allclose(np.asarray(tf(x, mode="fast")), np.asarray(x * 2))
    assert tf._compiled, "the non-failing static value must have been jitted"
    # a traceable structure with no kwargs also stays jitted
    np.testing.assert_allclose(np.asarray(tf(x)), np.asarray(x * 2))
    assert tf.uses_jit
    # the failing structure keeps working on repeat calls (cached eager key)
    np.testing.assert_allclose(np.asarray(tf(x, mode="concrete")), np.asarray(x))
    assert tf.uses_jit


def test_trace_failure_isolated_by_shape():
    """A blacklisted signature must not downgrade calls with different array shapes."""

    def f(x):
        if x.shape[0] == 2 and x[0] > 0:  # concretization error only for shape-2 inputs
            return x
        return x * 2

    tf = TracedFunction(f, jit="auto")
    np.testing.assert_allclose(np.asarray(tf(jnp.ones(2))), np.ones(2))  # eager fallback
    assert tf.uses_jit
    np.testing.assert_allclose(np.asarray(tf(jnp.ones(3))), 2 * np.ones(3))
    assert tf._compiled, "a different shape must still compile"


def test_runtime_errors_propagate_without_blacklist(monkeypatch):
    """An exception from an already-compiled executable must raise, not blacklist."""

    def f(x):
        return x

    tf = TracedFunction(f, jit="auto")

    def boom(static_names):
        def g(*args, **kwargs):
            raise RuntimeError("transient device hiccup")

        return g

    monkeypatch.setattr(tf, "_get_compiled", boom)
    with pytest.raises(RuntimeError, match="hiccup"):
        tf(jnp.ones(2))
    assert not tf._trace_failed_keys
    assert tf.uses_jit


def test_non_jax_inputs_still_latch_eager():
    """Opaque model objects can never trace: the permanent-eager path is preserved."""

    class Opaque:
        pass

    def f(m):
        return m

    tf = TracedFunction(f, jit="auto")
    tf(Opaque())
    assert not tf.uses_jit


# ---------------------------------------------------------------- ring.py padding

def test_ring_attention_fully_padded_rows_emit_zeros():
    """ADVICE #2: a batch element with kv_len == 0 must produce all-zero output."""
    mesh = make_mesh({"data": 2, "sequence": 4})
    rng = np.random.default_rng(3)
    q, k, v = (
        jnp.asarray(rng.normal(size=(4, 2, 32, 16)), dtype=jnp.float32) for _ in range(3)
    )
    kv_lens = jnp.asarray([0, 8, 32, 16], dtype=jnp.int32)
    shd = sequence_sharding(mesh)
    out = ring_attention(
        jax.device_put(q, shd),
        jax.device_put(k, shd),
        jax.device_put(v, shd),
        mesh,
        kv_lens=kv_lens,
    )
    out = np.asarray(out)
    # fully-masked batch element: exactly zero everywhere
    np.testing.assert_array_equal(out[0], np.zeros_like(out[0]))
    # partially-masked elements still match the reference (mask = k_pos < kv_len)
    k_pos = np.arange(32)
    mask = jnp.asarray(k_pos[None, None, None, :] < np.asarray(kv_lens)[:, None, None, None])
    ref = np.asarray(xla_attention(q, k, v, mask=mask))
    np.testing.assert_allclose(out[1:], ref[1:], atol=1e-5)


# ---------------------------------------------------------------- schedule.py N/step

def test_cron_single_value_with_step_expands_as_range():
    """ADVICE #3: minute '5/15' means 5,20,35,50 — not just 5."""
    spec = parse_cron("5/15 * * * *")
    assert spec.minutes == {5, 20, 35, 50}
    # ranges and stars with steps are unchanged
    assert parse_cron("0-30/10 * * * *").minutes == {0, 10, 20, 30}
    assert parse_cron("*/20 * * * *").minutes == {0, 20, 40}


# ---------------------------------------------------------------- dp.py ragged batches

def test_batches_pads_degenerate_batch_for_mesh():
    """ADVICE #5: a short batch on a mesh pads up to the data axis instead of crashing."""
    mesh = make_mesh({"data": 8})
    X = np.arange(12, dtype=np.float32).reshape(3, 4)  # 3 rows < batch_size
    y = np.arange(3, dtype=np.float32)
    out = list(batches(X, y, batch_size=16, mesh=mesh))
    assert len(out) == 1
    bx, by = out[0]
    assert bx.shape[0] % 8 == 0 and by.shape[0] % 8 == 0
    np.testing.assert_allclose(np.asarray(bx)[:3], X)
    # fill rows are WRAPPED real rows, never fabricated zeros
    np.testing.assert_allclose(np.asarray(bx)[3], X[0])
    np.testing.assert_allclose(np.asarray(by)[3:6], y)


def test_fit_prefetch_ragged_tail_on_mesh():
    """The prefetch path must rescue ragged tail batches onto the mesh too."""
    from unionml_tpu.models import MLPClassifier, create_train_state, fit

    rng = np.random.default_rng(0)
    n = 81  # 81 % 16 = ragged 1-row tail; 1 % 8 != 0 on the mesh
    data = {
        "inputs": rng.normal(size=(n, 8)).astype(np.float32),
        "labels": rng.integers(0, 2, size=n).astype(np.int32),
    }
    mesh = make_mesh({"data": 8})
    model = MLPClassifier(hidden_sizes=(8,), num_classes=2)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8)))
    state = create_train_state(model, params, learning_rate=1e-2)
    result = fit(
        state, data, batch_size=16, num_epochs=1, mesh=mesh, prefetch=True, log_every=1000
    )
    assert result.steps > 0


def test_dict_batches_pads_degenerate_batch_for_mesh():
    from unionml_tpu.models.training import dict_batches

    mesh = make_mesh({"data": 8})
    data = {"x": np.ones((5, 2), dtype=np.float32), "y": np.zeros((5,), dtype=np.float32)}
    out = list(dict_batches(data, batch_size=16, mesh=mesh))
    assert len(out) == 1
    assert out[0]["x"].shape[0] % 8 == 0


# ---------------------------------------------------------------- model.py thread safety

def _build_threshold_model(name: str) -> Model:
    dataset = Dataset(name=f"{name}_ds", features=["x"], targets=["y"])

    @dataset.reader
    def reader(n: int = 24) -> pd.DataFrame:
        rng = np.random.default_rng(0)
        x = rng.normal(size=n).astype(np.float32)
        return pd.DataFrame({"x": x, "y": (x > 0).astype(np.float32)})

    model = Model(name=name, init=lambda **hp: {"t": 0.0, **hp}, dataset=dataset)

    @model.trainer
    def trainer(m: dict, X: pd.DataFrame, y: pd.DataFrame, *, bias: float = 0.0) -> dict:
        return {"t": float(X["x"].median()) + bias}

    @model.predictor
    def predictor(m: dict, X: pd.DataFrame) -> np.ndarray:
        return (X["x"].to_numpy() > m["t"]).astype(np.float32)

    @model.evaluator
    def evaluator(m: dict, X: pd.DataFrame, y: pd.DataFrame) -> float:
        return float(np.mean(predictor(m, X) == y["y"].to_numpy()))

    return model


def test_adhoc_hyperparameters_do_not_mutate_model_state():
    """VERDICT weak #7: train with an ad-hoc hp dict leaves shared config untouched."""
    model = _build_threshold_model("hp_pure")
    assert model._hyperparameter_config is None
    model.train(hyperparameters={"lr": 0.1, "layers": 2})
    assert model._hyperparameter_config is None
    assert model.artifact is not None
    hp = model.artifact.hyperparameters
    assert {"lr": 0.1, "layers": 2} == (
        hp if isinstance(hp, dict) else {"lr": hp.lr, "layers": hp.layers}
    )


def test_concurrent_train_with_adhoc_hyperparameters():
    """Two threads training the same Model with different ad-hoc hp dicts must not race."""
    model = _build_threshold_model("hp_race")
    model.train()  # build stages once up front so threads exercise only the hp path
    barrier = threading.Barrier(2)
    errors = []

    def run(hp):
        try:
            barrier.wait(timeout=30)
            for _ in range(5):
                model.train(hyperparameters=hp)
        except Exception as exc:  # pragma: no cover - failure path
            errors.append(exc)

    threads = [
        threading.Thread(target=run, args=({"alpha": 1.0},)),
        threading.Thread(target=run, args=({"beta": 2, "gamma": "g"},)),
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errors
    assert model._hyperparameter_config is None


# ---------------------------------------------------------------- predict with defaults

def test_predict_zero_args_with_fully_defaulted_reader():
    """ADVICE #4 (serving {"inputs": {}}): zero-arg predict runs the reader defaults."""
    model = _build_threshold_model("zero_arg")
    model.train()
    preds = model.predict()
    assert len(preds) == 24


def test_predict_zero_args_rejected_when_reader_needs_args():
    dataset = Dataset(name="needs_args_ds", features=["x"], targets=["y"])

    @dataset.reader
    def reader(path: str) -> pd.DataFrame:  # required arg: zero-arg predict invalid
        raise AssertionError("should not be called")

    model = Model(name="needs_args", init=lambda: {}, dataset=dataset)

    @model.trainer
    def trainer(m: dict, X: pd.DataFrame, y: pd.DataFrame) -> dict:
        return m

    @model.predictor
    def predictor(m: dict, X: pd.DataFrame) -> np.ndarray:
        return np.zeros(1)

    @model.evaluator
    def evaluator(m: dict, X: pd.DataFrame, y: pd.DataFrame) -> float:
        return 0.0

    from unionml_tpu.model import ModelArtifact

    model.artifact = ModelArtifact({}, None, None)
    with pytest.raises(ValueError, match="features or \\*\\*reader_kwargs"):
        model.predict()


def test_attribute_error_during_trace_falls_back_eagerly():
    """Round-wide review regression: numpy-only methods on tracers (AttributeError)
    must fall back per call signature, like other trace-time failures."""

    def f(x):
        return np.frombuffer(x.tobytes(), dtype=np.float32)  # tracers have no tobytes

    tf = TracedFunction(f, jit="auto")
    out = tf(jnp.asarray([1.0, 2.0]))
    np.testing.assert_allclose(np.asarray(out), [1.0, 2.0])
    assert tf.uses_jit  # fallback was per-signature, not a permanent downgrade


# ---------------------------------------------------------------- attention.py packed padding

def _packed_qkv(rng, batch=2, heads=2, seq=128, dim=64):
    q, k, v = (
        jnp.asarray(rng.normal(size=(batch, heads, seq, dim)), dtype=jnp.float32)
        for _ in range(3)
    )
    return q, k, v


def test_flash_packed_fully_padded_rows_emit_zeros():
    """Round-3 ADVICE #1: fully-masked padding query rows (segment id 0) must emit
    zeros — scores == new_max == -inf made exp() emit 1 per slot, so the row
    produced a uniform V-average instead."""
    from unionml_tpu.ops.attention import flash_attention

    rng = np.random.default_rng(7)
    q, k, v = _packed_qkv(rng)
    seg = np.zeros((2, 128), dtype=np.int32)
    seg[0, :40] = 1
    seg[0, 40:100] = 2  # row 0: 28 padding positions
    seg[1, :128] = 1    # row 1: no padding
    seg = jnp.asarray(seg)
    out = flash_attention(q, k, v, segment_ids=seg, interpret=True)
    out = np.asarray(out)
    np.testing.assert_array_equal(out[0, :, 100:], np.zeros_like(out[0, :, 100:]))
    ref = np.asarray(xla_attention(q, k, v, segment_ids=seg))
    np.testing.assert_allclose(out, ref, atol=2e-5)


def test_flash_packed_interior_zero_segment_ids_match_xla():
    """Round-3 ADVICE #3: hand-built segment ids with INTERIOR zeros (padding not a
    contiguous suffix) must degrade to masking, not silently skip live KV blocks."""
    from unionml_tpu.ops.attention import flash_attention

    rng = np.random.default_rng(11)
    q, k, v = _packed_qkv(rng)
    seg = np.zeros((2, 128), dtype=np.int32)
    seg[0, :30] = 1
    seg[0, 60:128] = 2  # interior zero gap at 30:60; live keys run to the end
    seg[1, 10:120] = 1  # leading AND trailing zeros
    seg = jnp.asarray(seg)
    out = np.asarray(flash_attention(q, k, v, segment_ids=seg, interpret=True))
    ref = np.asarray(xla_attention(q, k, v, segment_ids=seg))
    np.testing.assert_allclose(out, ref, atol=2e-5)
    # and the gradient path: same check through the pallas backward
    def loss_flash(q_):
        return jnp.sum(flash_attention(q_, k, v, segment_ids=seg, interpret=True) ** 2)

    def loss_xla(q_):
        return jnp.sum(xla_attention(q_, k, v, segment_ids=seg) ** 2)

    g_flash = np.asarray(jax.grad(loss_flash)(q))
    g_xla = np.asarray(jax.grad(loss_xla)(q))
    np.testing.assert_allclose(g_flash, g_xla, atol=5e-4)


def test_attention_rejects_segment_ids_with_kv_lens_consistently():
    """Round-3 ADVICE #4: the segment_ids/kv_lens mutual exclusion must hold for
    every impl — previously impl='xla' silently combined both masks."""
    from unionml_tpu.ops.attention import attention

    rng = np.random.default_rng(13)
    q, k, v = _packed_qkv(rng, batch=1, heads=1, seq=16, dim=8)
    seg = jnp.ones((1, 16), dtype=jnp.int32)
    lens = jnp.asarray([8], dtype=jnp.int32)
    for impl in ("auto", "xla", "pallas"):
        with pytest.raises(ValueError, match="segment_ids already encodes padding"):
            attention(q, k, v, segment_ids=seg, kv_lens=lens, impl=impl)


# ---------------------------------------------------------------- round-4 ADVICE

class _tuning_tables:
    """Snapshot/restore the module-global dispatch tables around an overlay test."""

    def __enter__(self):
        from unionml_tpu.ops import tuning

        self.tuning = tuning
        self.saved = tuple(
            dict(t) for t in (tuning.MEASURED_IMPL, tuning.MEASURED_PACKED_IMPL,
                              tuning.TUNED_BLOCKS, tuning.PACKED_TUNED_BLOCKS)
        )
        return tuning

    def __exit__(self, *exc):
        t = self.tuning
        for table, saved in zip(
            (t.MEASURED_IMPL, t.MEASURED_PACKED_IMPL, t.TUNED_BLOCKS, t.PACKED_TUNED_BLOCKS),
            self.saved,
        ):
            table.clear()
            table.update(saved)


def test_tuning_overlay_validates_entries(tmp_path, monkeypatch):
    """Round-4 ADVICE #1: malformed overlay entries (unknown impl, non-int blocks)
    are dropped at load, not deferred to a confusing in-trace failure."""
    import json

    overlay = {
        "measured_impl": {"64,64,32": "pallas", "96,96,32": "cuda", "bad": "xla"},
        "tuned_blocks": {"64,64,32": [64, 64], "96,96,32": ["128", 128], "80,80,32": [64]},
        "measured_packed_impl": {"64,64,32": 7},
        "packed_tuned_blocks": {"64,64,32": [True, 64]},
    }
    path = tmp_path / "overlay.json"
    path.write_text(json.dumps(overlay))
    monkeypatch.setenv("UNIONML_TUNING_OVERLAY", str(path))
    with _tuning_tables() as tuning:
        tuning._apply_measured_overlay()
        assert tuning.MEASURED_IMPL[(64, 64, 32)] == "pallas"
        assert (96, 96, 32) not in tuning.MEASURED_IMPL  # unknown impl dropped
        assert tuning.TUNED_BLOCKS[(64, 64, 32)] == (64, 64)
        assert (96, 96, 32) not in tuning.TUNED_BLOCKS  # string block dropped
        assert (80, 80, 32) not in tuning.TUNED_BLOCKS  # wrong arity dropped
        assert (64, 64, 32) not in tuning.MEASURED_PACKED_IMPL  # non-str impl dropped
        assert (64, 64, 32) not in tuning.PACKED_TUNED_BLOCKS  # bool block dropped


def test_tuning_overlay_non_dict_tables_ignored(tmp_path, monkeypatch):
    """A table value of the wrong TYPE (list/str) must be ignored, not crash the
    module import that _apply_measured_overlay runs under."""
    import json

    path = tmp_path / "overlay.json"
    path.write_text(json.dumps({"tuned_blocks": [[64, 64]], "measured_impl": "xla"}))
    monkeypatch.setenv("UNIONML_TUNING_OVERLAY", str(path))
    with _tuning_tables() as tuning:
        before = dict(tuning.TUNED_BLOCKS)
        tuning._apply_measured_overlay()  # must not raise
        assert tuning.TUNED_BLOCKS == before


def test_tuning_overlay_non_dict_file_falls_through(tmp_path, monkeypatch):
    """A top-level-non-dict env-var overlay (valid JSON, wrong type) must fall
    through to the next candidate exactly like broken JSON syntax would."""
    path = tmp_path / "overlay.json"
    path.write_text("[]")
    monkeypatch.setenv("UNIONML_TUNING_OVERLAY", str(path))
    with _tuning_tables() as tuning:
        tuning._apply_measured_overlay()  # falls through to the repo root (no overlay committed)
        # the static table's verdict stands
        assert tuning.MEASURED_IMPL.get((128, 128, 64)) == "xla"


def test_tuning_overlay_ignores_cwd(tmp_path, monkeypatch):
    """Round-4 ADVICE #1: a TUNING_MEASURED.json in an unrelated working directory
    must not alter kernel dispatch (only the env var and the repo root load)."""
    import json

    poison = {"measured_impl": {"999,999,999": "pallas"}}
    (tmp_path / "TUNING_MEASURED.json").write_text(json.dumps(poison))
    monkeypatch.delenv("UNIONML_TUNING_OVERLAY", raising=False)
    monkeypatch.chdir(tmp_path)
    with _tuning_tables() as tuning:
        tuning._apply_measured_overlay()
        assert (999, 999, 999) not in tuning.MEASURED_IMPL


def test_flash_packed_bwd_seq_q_longer_than_kv():
    """Round-4 ADVICE #2: with seq_q > seq_k, live q rows beyond kv_len must still
    contribute to dk/dv — the legacy cdiv(kv_len, block_q) bound measured KV
    length in Q-block units and skipped those q blocks."""
    from unionml_tpu.ops.attention import flash_attention

    rng = np.random.default_rng(29)
    q = jnp.asarray(rng.normal(size=(2, 2, 128, 32)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 2, 64, 32)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 2, 64, 32)), jnp.float32)
    # duplicate segment ids: q rows 64..127 (seg 2) live beyond kv_len == 64
    segs = np.zeros((2, 128), np.int32)
    segs[:, :40] = 1
    segs[:, 40:128] = 2
    segs = jnp.asarray(segs)
    blocks = dict(block_q=16, block_k=16)

    def loss_flash(a, b, c):
        return jnp.sum(flash_attention(a, b, c, segment_ids=segs, interpret=True, **blocks) ** 2)

    def loss_xla(a, b, c):
        return jnp.sum(xla_attention(a, b, c, segment_ids=segs) ** 2)

    g_f = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_x = jax.grad(loss_xla, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip(("dq", "dk", "dv"), g_f, g_x):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4,
                                   err_msg=f"{name} mismatch")


def test_resident_device_latency_concurrent_first_calls_excluded():
    """Round-4 ADVICE #3: two requests racing on a NEW shape both pay (or wait on)
    the same trace+compile — neither may record into the steady-state window."""
    import threading

    from unionml_tpu.serving.resident import ResidentPredictor

    from .test_resident import _build_tokenized_model

    model = _build_tokenized_model()
    resident = ResidentPredictor(model, buckets=(4,), warmup=False)
    resident.setup()
    assert resident._compiled is not None

    inner = resident._compiled
    barrier = threading.Barrier(2, timeout=30)

    def gated(*args, **kwargs):
        barrier.wait()  # both requests are in-flight before either completes
        return inner(*args, **kwargs)

    resident._compiled = gated
    rows = [{"len": 3}]
    errors = []

    def run():
        try:
            resident.predict(features=rows)
        except Exception as exc:  # pragma: no cover - failure path
            errors.append(exc)

    threads = [threading.Thread(target=run) for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not errors
    assert list(resident._device_times_ms) == []  # both cold calls excluded
    resident._compiled = inner
    resident.predict(features=rows)  # warm-at-start: this one records
    assert len(resident._device_times_ms) == 1

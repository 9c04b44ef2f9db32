"""Native prefetcher tests: build, correctness vs python gather, fit() integration."""

import numpy as np
import pytest

from unionml_tpu.native import PrefetchLoader, native_available

pytestmark = pytest.mark.skipif(
    not native_available(), reason="native toolchain unavailable; python fallback covers behavior"
)


def _data(n=512, dim=16):
    rng = np.random.default_rng(0)
    return {
        "x": rng.normal(size=(n, dim)).astype(np.float32),
        "y": rng.integers(0, 4, size=(n,)).astype(np.int32),
    }


def test_prefetch_matches_python_gather():
    data = _data()
    loader = PrefetchLoader(data, batch_size=64, n_slots=3, n_threads=4)
    assert loader.uses_native
    perm = np.random.default_rng(7).permutation(512).astype(np.int64)
    seen = 0
    for b, batch in enumerate(loader.epoch(rng=np.random.default_rng(7))):
        idx = perm[b * 64 : (b + 1) * 64]
        np.testing.assert_array_equal(batch["x"], data["x"][idx])
        np.testing.assert_array_equal(batch["y"], data["y"][idx])
        seen += 1
    assert seen == 8
    loader.close()


def test_prefetch_slot_reuse_many_batches():
    """More batches than slots exercises the per-slot ordering constraint (deadlock regression)."""
    data = _data(n=2048)
    loader = PrefetchLoader(data, batch_size=64, n_slots=2, n_threads=4)
    for _ in range(2):  # two epochs reuse the same prefetcher
        count = sum(1 for _ in loader.epoch(rng=np.random.default_rng(1)))
        assert count == 32
    loader.close()


def test_prefetch_mismatched_rows_rejected():
    with pytest.raises(ValueError, match="leading dimension"):
        PrefetchLoader({"a": np.ones((4, 2)), "b": np.ones((5, 2))}, batch_size=2)


def test_fit_with_prefetch():
    import jax
    import jax.numpy as jnp

    from unionml_tpu.models import MLPClassifier, create_train_state, fit

    data = {
        "inputs": np.random.default_rng(0).normal(size=(256, 8)).astype(np.float32),
        "labels": np.random.default_rng(0).integers(0, 2, size=(256,)).astype(np.int32),
    }
    model = MLPClassifier(hidden_sizes=(16,), num_classes=2)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8)))
    state = create_train_state(model, params, learning_rate=1e-2)
    result = fit(state, data, batch_size=64, num_epochs=3, log_every=1000, prefetch=True)
    assert result.steps >= 9


def test_prefetch_drop_remainder_false_yields_true_tail():
    """Ragged tails come from the python gather, never out-of-bounds native reads."""
    data = _data(n=100)
    loader = PrefetchLoader(data, batch_size=64, n_slots=2, n_threads=2, drop_remainder=False)
    perm = np.random.default_rng(5).permutation(100).astype(np.int64)
    batches = []
    for b, batch in enumerate(loader.epoch(rng=np.random.default_rng(5))):
        batches.append({k: v.copy() for k, v in batch.items()})
    assert [len(b["x"]) for b in batches] == [64, 36]
    np.testing.assert_array_equal(batches[1]["x"], data["x"][perm[64:]])
    loader.close()


def test_prefetch_worker_side_dtype_conversion():
    """NEXT item 6: f64->f32 / i64->i32 / f32->bf16 convert inside the C++ workers."""
    import ml_dtypes

    rng = np.random.default_rng(7)
    data = {
        "f64": rng.normal(size=(40, 3)),                                  # float64
        "i64": rng.integers(0, 1000, size=(40,)).astype(np.int64),        # int64
        "f32": rng.normal(size=(40, 4)).astype(np.float32),               # float32
    }
    loader = PrefetchLoader(
        data,
        batch_size=8,
        n_slots=2,
        n_threads=2,
        convert={"f64": "float32", "i64": "int32", "f32": "bfloat16"},
    )
    perm = np.random.default_rng(9).permutation(40).astype(np.int64)
    first = next(iter(loader.epoch(rng=np.random.default_rng(9))))
    assert first["f64"].dtype == np.float32
    assert first["i64"].dtype == np.int32
    assert first["f32"].dtype == np.dtype(ml_dtypes.bfloat16)
    np.testing.assert_allclose(first["f64"], data["f64"][perm[:8]].astype(np.float32))
    np.testing.assert_array_equal(first["i64"], data["i64"][perm[:8]].astype(np.int32))
    # bf16 via round-to-nearest-even must equal numpy's own conversion
    np.testing.assert_array_equal(
        first["f32"], data["f32"][perm[:8]].astype(ml_dtypes.bfloat16)
    )
    loader.close()


def test_prefetch_copy_false_yields_python_owned_slots():
    """copy=False hands out the loader's own slot arrays (zero-copy consume)."""
    data = _data(n=64)
    loader = PrefetchLoader(data, batch_size=16, n_slots=2, n_threads=1)
    if not loader.uses_native:
        import pytest

        pytest.skip("native build unavailable")
    seen = []
    for batch in loader.epoch(rng=np.random.default_rng(0), copy=False):
        seen.append(id(batch["x"]))
    # the same slot buffers recycle (2 slots -> at most 2 distinct array objects)
    assert len(set(seen)) <= 2 and len(seen) == 4
    loader.close()


def test_prefetch_rejects_unknown_conversion():
    import pytest

    data = _data(n=16)
    with pytest.raises(ValueError, match="Unsupported native conversion"):
        PrefetchLoader(data, batch_size=8, convert={"x": "float16"})
    with pytest.raises(ValueError, match="unknown arrays"):
        PrefetchLoader(data, batch_size=8, convert={"nope": "float32"})


def test_prefetch_noop_conversion_accepted():
    """convert targeting the array's existing dtype is a plain gather, not an error."""
    data = _data(n=16)
    loader = PrefetchLoader(data, batch_size=8, convert={k: str(v.dtype) for k, v in data.items()})
    first = next(iter(loader.epoch()))
    for key, value in first.items():
        assert value.dtype == data[key].dtype
    loader.close()


def test_fit_prefetch_convert_handles_raw_pandas_dtypes():
    """fit(prefetch_convert=...) converts f64/i64 data in the native workers."""
    import jax
    import jax.numpy as jnp

    from unionml_tpu.models import MLPClassifier, create_train_state, fit

    rng = np.random.default_rng(0)
    data = {
        "inputs": rng.normal(size=(128, 8)),                        # float64 (pandas-style)
        "labels": rng.integers(0, 2, size=128).astype(np.int64),    # int64
    }
    model = MLPClassifier(hidden_sizes=(8,), num_classes=2)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8)))
    state = create_train_state(model, params, learning_rate=1e-2)
    result = fit(
        state, data, batch_size=32, num_epochs=2, log_every=10000, prefetch=True,
        prefetch_convert={"inputs": "float32", "labels": "int32"},
    )
    assert result.steps >= 8

    # the convert dict demonstrably reaches the loader: its validation fires on a
    # bad key / on use without prefetch (so dropping the plumbing fails this test)
    import pytest

    with pytest.raises(ValueError, match="unknown arrays"):
        fit(state, data, batch_size=32, num_epochs=1, prefetch=True,
            prefetch_convert={"typo": "float32"})
    with pytest.raises(ValueError, match="requires prefetch=True"):
        fit(state, data, batch_size=32, num_epochs=1, prefetch_convert={"inputs": "float32"})


def test_prefetch_deferred_release_lookahead():
    """defer_release=True: a held (unreleased) batch stays intact while the
    consumer pulls ahead — the transfer-overlap contract fit() relies on."""
    data = _data()
    loader = PrefetchLoader(data, batch_size=64, n_slots=4, n_threads=2)
    perm = np.random.default_rng(11).permutation(512).astype(np.int64)

    gen = loader.epoch(rng=np.random.default_rng(11), copy=False, defer_release=True)
    held = []
    for _ in range(3):  # hold 3 of 4 slots unreleased while pulling ahead
        held.append(next(gen))
    for b, (views, _) in enumerate(held):
        idx = perm[b * 64 : (b + 1) * 64]
        np.testing.assert_array_equal(views["x"], data["x"][idx])
    for views, release in held:
        release()
        release()  # idempotent
    seen = 3
    for views, release in gen:
        idx = perm[seen * 64 : (seen + 1) * 64]
        np.testing.assert_array_equal(views["x"], data["x"][idx])
        release()
        seen += 1
    assert seen == 8
    loader.close()


def test_prefetch_deferred_release_python_fallback():
    """The pure-python gather path honors the (views, release) contract too."""
    data = {k: v[:40] for k, v in _data().items()}
    loader = PrefetchLoader(data, batch_size=16, n_slots=2, n_threads=1, drop_remainder=False)
    pairs = list(loader.epoch(rng=np.random.default_rng(3), copy=True, defer_release=True))
    reference = list(loader.epoch(rng=np.random.default_rng(3), copy=True))
    assert len(pairs) == len(reference)
    for (views, release), ref in zip(pairs, reference):
        np.testing.assert_array_equal(views["x"], ref["x"])
        release()
    loader.close()


def test_library_path_keys_on_source_bytes_not_mtime(tmp_path, monkeypatch):
    """The build key is a hash of the sources: editing a byte moves the library
    path (an old build can never be loaded for new sources), touching an mtime
    does not (no rebuild, and no "newer file wins")."""
    import os
    import shutil

    import unionml_tpu.native as native_mod

    sources = tuple(
        shutil.copy(src, tmp_path / src.name) for src in native_mod._SOURCES
    )
    monkeypatch.setattr(native_mod, "_SOURCES", sources)
    original = native_mod._library_path()
    assert original.parent == native_mod.Path(native_mod.__file__).parent / "_build"

    future = sources[0].stat().st_mtime + 3600
    os.utime(sources[0], (future, future))
    assert native_mod._library_path() == original

    with open(sources[1], "ab") as fh:
        fh.write(b"\n// edited\n")
    assert native_mod._library_path() != original


def test_library_missing_symbols_degrades_to_python(tmp_path, monkeypatch):
    """A library at the keyed path that lacks a symbol (a corrupt or tampered
    build) degrades to the Python paths — never an AttributeError at a caller."""
    import subprocess

    import unionml_tpu.native as native_mod

    lib_path = tmp_path / "libunionml_native-test.so"
    subprocess.run(
        ["g++", "-O3", "-shared", "-fPIC", "-pthread", "-std=c++17",
         str(native_mod._SOURCES[0]), "-o", str(lib_path)],  # prefetch.cpp only: no upk_*
        check=True, capture_output=True,
    )
    monkeypatch.setattr(native_mod, "_library_path", lambda: lib_path)
    monkeypatch.setattr(native_mod, "_lib", None)
    monkeypatch.setattr(native_mod, "_build_failed", False)
    try:
        assert native_mod.load_native_library() is None
        assert not native_mod.native_available()
        from unionml_tpu.ops.packing import pack_sequences

        out = pack_sequences([np.arange(1, 5)], 8, impl="native")
        assert out["input_ids"].shape == (1, 8)
    finally:
        monkeypatch.setattr(native_mod, "_lib", None)
        monkeypatch.setattr(native_mod, "_build_failed", False)


def test_pack_rejects_short_token_buffer():
    """lengths summing past flat_tokens.size is the C++ OOB-read shape: the
    wrapper must reject it (None -> Python path), never call into upk_pack."""
    from unionml_tpu.native import pack_sequences_native

    flat = np.arange(5, dtype=np.int32)  # 5 tokens on the buffer...
    lengths = np.array([4, 6], dtype=np.int64)  # ...but lengths claim 10
    assert pack_sequences_native(flat, lengths, 8, 0, 0) is None
    # the aligned call still packs natively (the guard is precise, not a blanket)
    ok = pack_sequences_native(
        np.arange(10, dtype=np.int32), np.array([4, 6], dtype=np.int64), 8, 0, 0
    )
    assert ok is not None and ok["input_ids"].shape[0] >= 1

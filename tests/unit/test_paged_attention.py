"""Fused paged-attention kernel: interpret-mode parity + int8 edge cases.

Kernel level: the pallas arm (``interpret=True`` on CPU) against the XLA gather
reference — random pools first, then the three int8 edge shapes the pool
discipline actually produces: an EMPTY block (scale 0), a freshly RESCALED
tail block after a monotone scale grow, and a SPLICED shared-prefix block
borrowed at a non-zero table offset. The two arms attend over bit-identical
dequantized values and differ only in summation order (online-softmax over
blocks vs one dense softmax), so values are pinned tight but not bitwise;
what IS bitwise is each arm's invariance to content the contract says cannot
matter (masked columns, scale-0 codes, pool indirection).

Engine level: forcing ``paged_attn_impl="pallas"`` through the real decode /
chunked-prefill / speculative-verify programs produces TOKEN-IDENTICAL
streams to the XLA arm — greedy and fixed-seed sampled, f32 and int8 pools,
1- and 4-device meshes — and the steady state stays transfer-guard clean
with telemetry on (zero host→device uploads, ISSUE-18 acceptance).
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from unionml_tpu.models import gpt
from unionml_tpu.models.gpt import GPTLMHeadModel, _paged_append_quantized
from unionml_tpu.ops.paged_attention import paged_attention, walk_steps, xla_paged_attention
from unionml_tpu.parallel import make_mesh
from unionml_tpu.serving.continuous import DecodeEngine

from tests.unit.test_paged_kv import BS, mixed_schedule

HEADS, HD = 2, 16


# --------------------------------------------------------------- kernel level


def _rand_pool(seed, blocks, bs, *, quantized):
    """A filled pool: int8 codes + positive per-(block, head) scales, or f32."""
    rng = np.random.default_rng(seed)
    if quantized:
        k = jnp.asarray(rng.integers(-127, 128, (blocks, HEADS, bs, HD)), jnp.int8)
        v = jnp.asarray(rng.integers(-127, 128, (blocks, HEADS, bs, HD)), jnp.int8)
        ks = jnp.asarray(rng.uniform(0.005, 0.02, (blocks, HEADS, 1, 1)), jnp.float32)
        vs = jnp.asarray(rng.uniform(0.005, 0.02, (blocks, HEADS, 1, 1)), jnp.float32)
        return k, v, ks, vs
    k = jnp.asarray(rng.normal(size=(blocks, HEADS, bs, HD)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(blocks, HEADS, bs, HD)), jnp.float32)
    return k, v, None, None


def _q(seed, batch, S=1):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(batch, HEADS, S, HD)), jnp.float32)


def _run(impl, q, k, v, table, base, scales=(None, None), compute=jnp.float32):
    extra = {"interpret": True} if impl == "pallas" else {}
    return np.asarray(paged_attention(
        q, k, v, table, base, k_scale=scales[0], v_scale=scales[1],
        out_dtype=compute, impl=impl, **extra,
    ), np.float32)


def _both(q, k, v, table, base, ks=None, vs=None):
    return tuple(_run(impl, q, k, v, table, base, (ks, vs)) for impl in ("xla", "pallas"))


@pytest.mark.parametrize("quantized", [False, True], ids=["f32", "int8"])
def test_kernel_matches_xla_reference(quantized):
    """Random pool, ragged bases, decode (S=1) and chunk (S>1) shapes."""
    k, v, ks, vs = _rand_pool(0, blocks=9, bs=BS, quantized=quantized)
    table = jnp.asarray([[0, 1, 2, 8], [3, 4, 8, 8], [5, 6, 7, 8]], jnp.int32)
    base = jnp.asarray([11, 5, 9], jnp.int32)  # ragged live lengths
    ref, out = _both(_q(1, 3), k, v, table, base, ks, vs)
    np.testing.assert_allclose(out, ref, atol=2e-5)

    # batch-1 chunk: S query tokens at consecutive positions (prefill shape)
    ref, out = _both(_q(2, 1, S=6), k, v, table[:1], base[:1] - 4, ks, vs)
    np.testing.assert_allclose(out, ref, atol=2e-5)


def test_empty_block_scale_zero_is_inert():
    """Edge 1: an allocated-but-unwritten block (scale 0, arbitrary stale
    codes). Within the live range it must dequantize to exact zeros; past the
    base it is masked entirely. Either way the CODES cannot matter: flipping
    every stale byte leaves the kernel output bit-identical, and both arms
    agree on the attended values."""
    k, v, ks, vs = _rand_pool(3, blocks=6, bs=BS, quantized=True)
    empty = 4
    ks = ks.at[empty].set(0.0)
    vs = vs.at[empty].set(0.0)
    q = _q(4, 2)
    table = jnp.asarray([[0, 1, empty, 5], [2, empty, 3, 5]], jnp.int32)
    # row 0: empty block sits PAST base (masked); row 1: empty block sits
    # INSIDE the live range (scale-0 zeros participate in the softmax)
    base = jnp.asarray([2 * BS - 1, 3 * BS - 1], jnp.int32)

    ref, out = _both(q, k, v, table, base, ks, vs)
    np.testing.assert_allclose(out, ref, atol=2e-5)

    stale = jnp.full(k.shape[1:], 93, jnp.int8)  # flip every stale byte
    k2, v2 = k.at[empty].set(stale), v.at[empty].set(-stale)
    ref2, out2 = _both(q, k2, v2, table, base, ks, vs)
    np.testing.assert_array_equal(out2, out)
    np.testing.assert_array_equal(ref2, ref)


def test_rescaled_tail_block_after_monotone_grow():
    """Edge 2: a tail block built by the REAL append arithmetic, with a loud
    token forcing a mid-block scale grow (old codes requantized to the new,
    strictly larger scale). Both arms attend the requantized codes through the
    same dequant expression, so parity must hold on the exact bytes the pool
    discipline produces — not on synthetic well-scaled data."""
    k, v, ks, vs = _rand_pool(5, blocks=5, bs=BS, quantized=True)
    tail = 3
    rng = np.random.default_rng(6)
    dst = jnp.asarray([tail], jnp.int32)
    scale_log = []
    for off in range(BS):
        amp = 4.0 if off == 2 else 0.5  # off=2 is ~8x louder: forces the grow
        tok = jnp.asarray(amp * rng.normal(size=(1, HEADS, HD)), jnp.float32)
        k, ks = _paged_append_quantized(k, ks, dst, jnp.asarray([off], jnp.int32), tok)
        v, vs = _paged_append_quantized(v, vs, dst, jnp.asarray([off], jnp.int32), tok)
        scale_log.append(np.asarray(ks[tail, :, 0, 0]))
    # the discipline under test: per-head scales never shrank across appends
    for prev, cur in zip(scale_log, scale_log[1:]):
        assert (cur >= prev - 1e-12).all()
    assert (scale_log[2] > scale_log[1]).any()  # the loud token DID grow it

    table = jnp.asarray([[0, 1, 2, tail]], jnp.int32)
    ref, out = _both(_q(7, 1), k, v, table, jnp.asarray([4 * BS - 1]), ks, vs)
    np.testing.assert_allclose(out, ref, atol=2e-5)


def test_spliced_shared_block_at_nonzero_offset():
    """Edge 3: a shared-prefix block borrowed by another row at a NON-ZERO
    table column. The kernel walks each row's table independently, so sharing
    must be pure indirection: duplicating the shared block into a private copy
    changes nothing, bitwise, in either arm."""
    k, v, ks, vs = _rand_pool(8, blocks=8, bs=BS, quantized=True)
    shared, spare = 0, 6
    table = jnp.asarray([[shared, 1, 2, 7], [3, shared, 4, 7]], jnp.int32)
    base = jnp.asarray([3 * BS - 1, 3 * BS - 1], jnp.int32)
    q = _q(9, 2)

    ref, out = _both(q, k, v, table, base, ks, vs)
    np.testing.assert_allclose(out, ref, atol=2e-5)

    # physically duplicate the shared block for row 1: identical output bytes
    k2 = k.at[spare].set(k[shared])
    v2 = v.at[spare].set(v[shared])
    ks2 = ks.at[spare].set(ks[shared])
    vs2 = vs.at[spare].set(vs[shared])
    table2 = jnp.asarray([[shared, 1, 2, 7], [3, spare, 4, 7]], jnp.int32)
    ref2, out2 = _both(q, k2, v2, table2, base, ks2, vs2)
    np.testing.assert_array_equal(out2, out)
    np.testing.assert_array_equal(ref2, ref)


# the real block geometry (16-token blocks of 64-wide heads), so that a step of
# the kernel takes 8 table entries as it does on the chip. The joined pools are
# the engine's full-precision leaf, a head's key beside its value in rows of 128
# lanes: the leaf the kernel fetches with copies of its own, a step a live
# tile. The 64-wide leaves (what an int8 pool still is) take the BlockSpec walk.
RBS, RHD = 16, 64
POOLS = {
    "f32": jnp.float32, "bf16": jnp.bfloat16, "int8": jnp.int8,
    "joined": jnp.float32, "joined_bf16": jnp.bfloat16,
}


def _ragged_case(heads, width, S, pool, seed=0):
    """One ragged batch: bases 0, 1, ``bs - 1``, ``bs``, ``bs + 1``, mid-table,
    the full table, the engine's sentinel for a retired row, and an empty live
    range (no query sees a key). A row owns the blocks of its live columns; the
    columns past them all name the pool's last block, as the engine's name its
    scratch block."""
    rng = np.random.default_rng(seed)
    capacity = width * RBS
    bases = np.asarray(
        [0, 1, RBS - 1, RBS, RBS + 1, capacity // 2 + 3, capacity - S, (width - 1) * RBS, -S]
    )
    rows = len(bases)
    live = np.clip((bases + S - 1) // RBS + 1, 0, width)  # live columns a row
    blocks = int(live.sum()) + 1
    owned = iter(rng.permutation(blocks - 1))
    table = np.full((rows, width), blocks - 1, np.int32)
    for r in range(rows):
        table[r, : live[r]] = [next(owned) for _ in range(live[r])]
    shape = (blocks, heads, RBS, RHD)
    compute = jnp.bfloat16 if pool.endswith("bf16") else jnp.float32
    q = jnp.asarray(rng.normal(size=(rows, heads, S, RHD)), compute)
    scales = [None, None]
    if pool.startswith("joined"):
        k, v = jnp.asarray(rng.normal(size=shape[:3] + (2 * RHD,)), POOLS[pool]), None
    elif pool == "int8":
        k, v = (jnp.asarray(rng.integers(-127, 128, shape), jnp.int8) for _ in range(2))
        scales = [
            jnp.asarray(rng.uniform(0.005, 0.02, (blocks, heads, 1, 1)), jnp.float32)
            for _ in range(2)
        ]
    else:
        k, v = (jnp.asarray(rng.normal(size=shape), POOLS[pool]) for _ in range(2))
    return q, k, v, jnp.asarray(table), jnp.asarray(bases, jnp.int32), scales, compute


@pytest.mark.parametrize("heads", [12, 16])
@pytest.mark.parametrize("pool", sorted(POOLS))
@pytest.mark.parametrize("S", [1, 5, 24], ids=["decode", "verify", "chunk"])
@pytest.mark.parametrize("width", [65, 16], ids=["w65", "w16"])
def test_bounded_walk_matches_xla_over_ragged_rows(width, S, pool, heads):
    """The walk that ends at each row's live length against the full gather:
    every boundary of the block and of the 8-entry tile in one batch (a row on a
    block's edge, a retired row on the sentinel, a row with no key), a table
    whose width the tile does not divide (65: the last tile is short) and one it
    does (16). Both walks: a step a live tile over the joined leaf, the
    BlockSpec grid over the narrow ones."""
    q, k, v, table, base, scales, compute = _ragged_case(heads, width, S, pool)
    ref = _run("xla", q, k, v, table, base, scales, compute)
    out = _run("pallas", q, k, v, table, base, scales, compute)
    # the steps the walk takes: a tile for every 8 live columns of a row where
    # the kernel copies (one even for the row with no key), the whole table's
    # 9 (or 2) tiles a row where BlockSpecs fetch
    live = np.clip(np.asarray(base) + S - 1, 0, width * RBS - 1) // RBS
    want = (live // 8 + 1).sum() if v is None else len(live) * -(-width // 8)
    assert walk_steps(q, k, v, table, np.asarray(base), *scales) == want
    # a query that sees no key: the gather arm softmaxes a row of masks into a
    # uniform average, the kernel adds nothing and writes 0, as the masked full
    # walk did
    sees = (np.asarray(base)[:, None] + np.arange(S)[None, :]) >= 0  # (rows, S)
    assert np.isfinite(out).all()
    assert (out[~sees[:, None, :].repeat(heads, 1)] == 0).all()
    live = sees[:, None, :, None]
    # bf16: the arms round the weights at different points (after and before
    # the normalisation), one unit of bf16 in the last place apart
    tol = 2e-2 if pool.endswith("bf16") else 2e-5
    np.testing.assert_allclose(
        np.where(live, out, 0), np.where(live, ref, 0), atol=tol, rtol=tol
    )


@pytest.mark.parametrize("pool", sorted(POOLS))
@pytest.mark.parametrize("S", [1, 24], ids=["decode", "chunk"])
def test_nothing_past_the_live_length_is_folded_in(S, pool):
    """Poison everything past each row's last visible key — NaN in float pools
    (the rest of the last live block included), codes of +-127 under a scale
    that dequantizes to infinity in whole dead int8 blocks — and the output
    does not move: dead columns are neither fetched nor multiplied in."""
    heads, width = 12, 65
    q, k, v, table, base, scales, compute = _ragged_case(heads, width, S, pool, seed=1)
    clean = _run("pallas", q, k, v, table, base, scales, compute)

    last = np.asarray(base) + S - 1  # last key position a row's queries see
    position = np.arange(width * RBS).reshape(width, RBS)
    dead_key = position[None] > last[:, None, None]  # (rows, width, bs)
    blocks = np.asarray(table)
    dead_blocks = np.unique(blocks[dead_key.all(-1)])  # the shared last block, no other
    if pool == "int8":
        k, v = np.array(k), np.array(v)
        k[dead_blocks], v[dead_blocks] = 127, -127
        scales = [np.array(s) for s in scales]
        for s in scales:
            s[dead_blocks] = 3e38
        scales = [jnp.asarray(s) for s in scales]
    else:
        k, v = (leaf if leaf is None else np.array(leaf, np.float32) for leaf in (k, v))
        for leaf in (k, v)[: 1 if v is None else 2]:
            picked = leaf[blocks]  # (rows, width, heads, bs, hd); rows share no live block
            picked[np.broadcast_to(dead_key[:, :, None, :, None], picked.shape)] = np.nan
            leaf[blocks] = picked
    poisoned = _run(
        "pallas", q, jnp.asarray(k, POOLS[pool]), v if v is None else jnp.asarray(v, POOLS[pool]),
        table, base, scales, compute,
    )
    assert np.isfinite(poisoned).all()
    np.testing.assert_array_equal(poisoned, clean)


def test_impl_validation():
    k, v, ks, vs = _rand_pool(0, blocks=2, bs=BS, quantized=True)
    with pytest.raises(ValueError, match="impl"):
        paged_attention(_q(0, 1), k, v, jnp.zeros((1, 1), jnp.int32),
                        jnp.zeros((1,), jnp.int32), impl="cuda")
    with pytest.raises(ValueError, match="together"):
        paged_attention(_q(0, 1), k, v, jnp.zeros((1, 1), jnp.int32),
                        jnp.zeros((1,), jnp.int32), k_scale=ks)
    # the kernel off a TPU is an error, never a silent interpreter run: only an
    # explicit interpret=True (these tests) may execute it on the CPU
    with pytest.raises(RuntimeError, match="needs a TPU backend"):
        paged_attention(_q(0, 1), k, v, jnp.zeros((1, 1), jnp.int32),
                        jnp.zeros((1,), jnp.int32), k_scale=ks, v_scale=vs, impl="pallas")


# --------------------------------------------------------------- engine level


@pytest.fixture(autouse=True)
def interpret_kernel(monkeypatch):
    """The model calls ``paged_attention`` with no ``interpret`` argument (the
    serving path never derives it); these CPU tests bind it on."""
    monkeypatch.setattr(
        gpt, "paged_attention", functools.partial(paged_attention, interpret=True)
    )


ENGINE_KW = dict(
    num_slots=4, max_len=64, prefill_buckets=(4, 8, 16), prefill_chunk=4,
    prefix_cache_blocks=24, prefix_block_size=BS, seed=0, temperature=0.0,
)


def _engine(gpt_tiny_session, impl, *, mesh=None, **kw):
    """A paged engine whose model config pins the decode-attention backend
    (same variables — the weights don't know which kernel attends them)."""
    config, _, variables = gpt_tiny_session
    model = GPTLMHeadModel(dataclasses.replace(config, paged_attn_impl=impl))
    return DecodeEngine(model, variables, paged=True, mesh=mesh,
                        **dict(ENGINE_KW, **kw))


@pytest.mark.parametrize("kv", [None, "int8"], ids=["f32pool", "int8pool"])
@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "sampled"])
def test_engine_kernel_token_parity(gpt_tiny_session, kv, sampled):
    """Fused kernel == XLA arm, token for token, through the full mixed
    schedule (miss, splice hit, chunked prefill, mid-flight cancel, replay)."""
    streams = {}
    for impl in ("xla", "pallas"):
        eng = _engine(gpt_tiny_session, impl, kv_quantize=kv)
        streams[impl], _ = mixed_schedule(eng, sampled=sampled)
    assert streams["pallas"] == streams["xla"]


def test_engine_kernel_token_parity_mesh4(gpt_tiny_session):
    """Same gate under a 4-device tensor mesh (int8 pool): the kernel runs
    shard-local inside the pjit program on every device."""
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices (conftest forces 8 CPU devices)")
    streams = {}
    for impl in ("xla", "pallas"):
        mesh = make_mesh({"tensor": 4}, devices=jax.devices()[:4])
        eng = _engine(gpt_tiny_session, impl, mesh=mesh, kv_quantize="int8")
        streams[impl], _ = mixed_schedule(eng, sampled=False)
    assert streams["pallas"] == streams["xla"]


def test_spec_verify_token_parity(gpt_tiny_session):
    """Speculative schedule: the S-token paged VERIFY path also dispatches to
    the kernel; spec engines on either backend emit identical streams."""
    from unionml_tpu.serving.speculative import SpeculativeEngine

    config, _, variables = gpt_tiny_session
    streams = {}
    for impl in ("xla", "pallas"):
        model = GPTLMHeadModel(dataclasses.replace(config, paged_attn_impl=impl))
        eng = SpeculativeEngine(model, variables, model, variables,
                                **dict(ENGINE_KW, seed=7))
        streams[impl], _ = mixed_schedule(eng, sampled=False)
    assert streams["pallas"] == streams["xla"]


def test_kernel_steady_state_transfer_guard_clean_with_telemetry(gpt_tiny_session):
    """ISSUE-18 acceptance: with telemetry ON and the fused kernel forced, the
    steady-state decode tick still pays ZERO host→device uploads — the kernel's
    scalar-prefetch operands (table, bases) are the same device-resident
    mirrors the XLA path reads, and the impl info gauge is host-only."""
    from unionml_tpu.serving.telemetry import Telemetry

    tel = Telemetry()
    eng = _engine(gpt_tiny_session, "pallas", kv_quantize="int8", telemetry=tel)
    eng.admit_many([([3, 1, 4, 1], 20, {}), ([2, 7, 1, 8], 20, {})])
    eng.step()  # compile + warm outside the guard
    eng.step()
    with jax.transfer_guard_host_to_device("disallow"):
        for _ in range(3):
            eng.step()
    rendered = tel.metrics.render()
    assert 'unionml_paged_attn_impl{impl="pallas"} 1' in rendered


@pytest.mark.parametrize("pool", ["joined", "int8", "xla"])
def test_kernel_grid_steps_counts_the_tiles_each_dispatch_walks(pool):
    """``/stats`` ``generation.pipeline.kernel_grid_steps``: every dispatch adds
    the steps one layer's call of the decode kernel takes over the step's rows,
    x the burst's steps. Over the joined 128-wide leaf (heads of 64) that is a
    step for every 8 live table columns of a row, a retired row's on the
    sentinel (the whole table) among them; over an int8 pool's narrow leaves the
    table's tiles for every row, whatever it holds; the gather arm adds 0."""
    from unionml_tpu.models.gpt import GPTConfig, init_params

    block, slots, max_len = 16, 3, 1024
    config = GPTConfig.tiny(
        hidden_size=128, num_heads=2, num_layers=1, max_position_embeddings=1024, dropout=0.0,
        dtype=jnp.float32,
        attention_impl="xla", paged_attn_impl="xla" if pool == "xla" else "pallas",
    )
    engine = DecodeEngine(
        GPTLMHeadModel(config), init_params(config, seq_len=16), num_slots=slots, max_len=max_len,
        prefill_buckets=(4, 8, 16), prefix_block_size=block, paged=True,
        kv_quantize="int8" if pool == "int8" else None,
    )
    width = engine._table_width
    # 512 KB of two heads' 8 KB f32 blocks is 32 entries a step of the copied
    # walk; 8 entries fill the lanes of the BlockSpec walk; the table has 65
    tile = 32 if pool == "joined" else 8
    expected, dispatch = [0], engine._dispatch_step

    def counting_dispatch(lookahead):
        lens = np.where(engine._active, engine._lens_host, (width - 1) * block)
        out = dispatch(lookahead)
        live = np.clip(lens, 0, width * block - 1) // block
        steps = {"joined": (live // tile + 1).sum(), "int8": slots * -(-width // tile), "xla": 0}
        expected[0] += int(steps[pool]) * out[3]
        return out

    engine._dispatch_step = counting_dispatch
    engine.admit_many([([3, 1, 4, 1, 5, 9, 2, 6, 5], 9, {}), ([2, 7], 30, {})])
    while engine._active.any():
        engine.step()
    stats = engine.pipeline_stats()
    assert stats["kernel_grid_steps"] == expected[0]
    if pool == "joined":
        # a decoding row is one tile, a retired one the table's three
        steps, rows = stats["kernel_grid_steps"], stats["active_slot_steps"]
        assert 3 * stats["step_dispatches"] < steps == rows + 3 * (3 * stats["step_dispatches"] - rows)


# ------------------------------------- fewer key heads, keys that are the values


def _shared_key_case(S, heads=4, key_heads=1, width=9, dim=48, bases=(0, 5, 37, 143 - 24, 8 * 16), seed=0):
    """A latent-style pool: ``key_heads`` key heads for ``heads`` query heads,
    no value leaf; ragged rows, the engine's sentinel among them."""
    rng = np.random.default_rng(seed)
    bases = np.asarray(bases)
    rows = len(bases)
    live = np.clip((bases + S - 1) // RBS + 1, 0, width)
    blocks = int(live.sum()) + 1
    owned = iter(rng.permutation(blocks - 1))
    table = np.full((rows, width), blocks - 1, np.int32)
    for r in range(rows):
        table[r, : live[r]] = [next(owned) for _ in range(live[r])]
    k = jnp.asarray(rng.normal(size=(blocks, key_heads, RBS, dim)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(rows, heads, S, dim)), jnp.float32)
    return q, k, jnp.asarray(table), jnp.asarray(bases, jnp.int32)


def _dense_oracle(q, k, v, table, base, sm_scale):
    """The semantics spelt out row by row in numpy: query head ``h`` reads key
    head ``h // group``; ``v=None`` weighs the keys themselves."""
    q, k, table, base = (np.asarray(x) for x in (q, k, table, base))
    v = k if v is None else np.asarray(v)
    rows, heads, S, _ = q.shape
    group = heads // k.shape[1]
    out = np.zeros((rows, heads, S, v.shape[-1]), np.float64)
    for b in range(rows):
        keys = np.concatenate([k[i] for i in table[b]], axis=1)  # (key_heads, capacity, dim)
        values = np.concatenate([v[i] for i in table[b]], axis=1)
        for h in range(heads):
            for s in range(S):
                n = min(base[b] + s + 1, keys.shape[1])
                scores = keys[h // group, :n] @ q[b, h, s] * sm_scale
                weights = np.exp(scores - scores.max())
                out[b, h, s] = weights / weights.sum() @ values[h // group, :n]
    return out


@pytest.mark.parametrize("S", [1, 24], ids=["decode", "chunk"])
@pytest.mark.parametrize("key_heads", [1, 2])
def test_shared_key_heads_and_keys_as_values_both_arms(key_heads, S):
    """One key head for four query heads (and two for four), the keys the
    values too, a scale of the caller's: the kernel and the gather arm against
    the definition. float32 throughout, so 2e-5 is summation order alone."""
    q, k, table, base = _shared_key_case(S, key_heads=key_heads)
    want = _dense_oracle(q, k, None, table, base, 0.3)
    for impl in ("xla", "pallas"):
        extra = {"interpret": True} if impl == "pallas" else {}
        out = paged_attention(q, k, None, table, base, impl=impl, sm_scale=0.3, **extra)
        assert out.shape == q.shape  # the caller slices its values off the key row
        np.testing.assert_allclose(np.asarray(out), want, atol=2e-5, rtol=2e-5)
    # a value leaf of its own, narrower than the keys, under shared key heads
    v = k[..., :16] * 2.0
    want = _dense_oracle(q, k, v, table, base, 48 ** -0.5)
    for impl in ("xla", "pallas"):
        extra = {"interpret": True} if impl == "pallas" else {}
        out = paged_attention(q, k, v, table, base, impl=impl, **extra)
        np.testing.assert_allclose(np.asarray(out), want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dim", [48, 128], ids=["blockspec", "copied"])
@pytest.mark.parametrize("limit,S,want_rows", [(1400 * 1024, 16, 32), (900 * 1024, 16, 8)],
                         ids=["whole_spans", "part_of_a_span"])
def test_query_rows_split_into_blocks_when_one_heads_rows_outgrow_vmem(monkeypatch, limit, S, want_rows, dim):
    """32 query heads of a long chunk over one key head are too many rows for
    one step: the rows split into blocks of whole spans, or of a divisor of
    one, each with its own last visible key and so its own walk. Forced at a
    small size by a small VMEM budget, over a narrow row (BlockSpecs) and one
    128 lanes wide (copies); the result is the definition's either way."""
    from unionml_tpu.ops import paged_attention as module

    monkeypatch.setattr(module, "_VMEM_LIMIT_BYTES", limit)
    q, k, table, base = _shared_key_case(S, bases=(0, 7, 60, 128), dim=dim)
    copied = dim % 128 == 0
    heads, rows, tile = module._tiling(1, 4 * S, S, RBS, dim, 9, 4, False, copied)
    assert (heads, rows, tile) == (1, want_rows, 8)
    # the jitted forward keeps a trace a shape: none made under another budget
    # may serve this call, and none made under this one a later test
    module._paged_forward.clear_cache()
    try:
        out = paged_attention(q, k, None, table, base, impl="pallas", interpret=True)
    finally:
        module._paged_forward.clear_cache()
    want = _dense_oracle(q, k, None, table, base, dim ** -0.5)
    np.testing.assert_allclose(np.asarray(out), want, atol=2e-5, rtol=2e-5)
    # a step for every tile of every row block: a block of whole spans sees the
    # row's last key, a part of a span the last of its own positions
    blocks = 4 * S // rows
    first = (np.arange(blocks) * rows) % S
    last = np.asarray(base)[:, None] + (first + rows - 1 if rows < S else np.full(blocks, S - 1))
    steps = (np.clip(last, 0, 9 * RBS - 1) // RBS // 8 + 1).sum() if copied else 4 * blocks * 2
    assert walk_steps(q, k, None, table, np.asarray(base)) == steps


def test_per_head_shapes_take_the_path_they_took():
    """GPT-2's calls are what they were: the tiling of its shapes over the joined
    128-wide leaf (all heads, the query's own rows, 8 table entries a step: its
    512 KB; 4 heads of a 512-token chunk) and over an int8 pool's 64-wide codes;
    the latent leaf's 160 KB blocks go three a step in decode, where the lanes
    alone gave one, and one a step under a chunk's split rows; four local heads
    of a ``tensor`` shard take as many more entries as their blocks are smaller. And the gather arm bit
    for bit the historical formula (gather, flatten, ``xla_attention`` under the
    positional mask)."""
    from unionml_tpu.ops import paged_attention as module
    from unionml_tpu.ops.attention import xla_attention

    assert module._tiling(16, 1, 1, 16, 128, 65, 2, False, True) == (16, 1, 8)
    assert module._tiling(12, 1, 1, 16, 64, 65, 1, True) == (12, 1, 8)
    assert module._tiling(16, 64, 64, 16, 128, 65, 2, False, True) == (16, 64, 8)
    assert module._tiling(16, 512, 512, 16, 128, 65, 2, False, True) == (4, 512, 8)
    assert module._tiling(1, 32, 1, 128, 640, 65, 2, False, True) == (1, 32, 3)
    assert module._tiling(1, 32 * 1024, 1024, 128, 640, 65, 2, False, True) == (1, 512, 1)
    assert module._tiling(4, 1, 1, 16, 128, 65, 2, False, True) == (4, 1, 32)
    q, k, v, table, base, _, _ = _ragged_case(12, 16, 5, "bf16")
    rows, heads, S, dim = q.shape
    capacity = table.shape[1] * RBS
    flat = lambda leaf: jnp.moveaxis(leaf[table], 2, 1).reshape(rows, heads, capacity, dim)
    q_pos = base[:, None] + jnp.arange(S)[None, :]
    mask = (jnp.arange(capacity)[None, None, :] <= q_pos[:, :, None])[:, None]
    historical = xla_attention(q, flat(k), flat(v), mask=mask)
    np.testing.assert_array_equal(
        np.asarray(xla_paged_attention(q, k, v, table, base), np.float32),
        np.asarray(historical, np.float32),
    )

"""TPU-platform lowering gate for the pallas kernels — runs on CPU.

Interpret-mode-correct pallas code has failed MOSAIC LOWERING on first hardware
contact twice (the flash kernel's rank-1 SMEM block of size 1; the paged
kernel's (1, heads) scale blocks) — a class of bug CPU interpret tests cannot
see. ``jax.export`` with
``platforms=["tpu"]`` runs the real pallas→Mosaic lowering (where that failure
occurred) without needing a TPU device, so these tests catch lowering
regressions in every CPU CI run. Every FLASH-KERNEL check asserts
``tpu_custom_call`` is in the exported module — export SUCCEEDING is not
enough, because ``flash_attention`` silently falls back to the XLA path for
unliftable configs and that exports fine too. The two PROGRAM-level checks
differ deliberately: the headline train step asserts Mosaic-kernel
presence/absence CONSISTENT with the measured dispatch verdict, and the
sharded-parallelism programs (pure XLA collectives, no pallas) assert export
success only. The paged-attention cases go one step further where libtpu can
describe a v5e host without one being attached: they run the Mosaic compiler
itself, ahead of time. What none of these prove is runtime numerics, which
``chip_smoke.py`` checks on the chip.
"""

import jax
from jax import export as jax_export
import jax.numpy as jnp
import numpy as np
import pytest

from unionml_tpu.ops.attention import flash_attention


def _assert_mosaic_lowered(fn, *args):
    exported = jax_export.export(jax.jit(fn), platforms=["tpu"])(*args)
    mlir = exported.mlir_module()
    # the pallas kernel lowers to a Mosaic tpu_custom_call; its absence means the
    # call silently routed to the XLA fallback and this test would be vacuous
    assert "tpu_custom_call" in mlir, "no Mosaic custom call: XLA fallback was exported"
    return exported


def _qkv(batch=2, heads=4, seq=256, dim=64, dtype=jnp.bfloat16, seq_kv=None):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(batch, heads, seq, dim)), dtype)
    kv_shape = (batch, heads, seq_kv if seq_kv is not None else seq, dim)
    k = jnp.asarray(rng.normal(size=kv_shape), dtype)
    v = jnp.asarray(rng.normal(size=kv_shape), dtype)
    return q, k, v


def _segments(batch=2, seq=256):
    seg = np.zeros((batch, seq), np.int32)
    seg[:, : seq // 3] = 1
    seg[:, seq // 3 : (9 * seq) // 10] = 2  # padding tail after segment 2
    return jnp.asarray(seg)


@pytest.mark.parametrize("block_q,block_k", [(128, 128), (256, 128), (256, 256)])
def test_dense_flash_lowers_for_tpu(block_q, block_k):
    q, k, v = _qkv()

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=block_q, block_k=block_k)

    _assert_mosaic_lowered(fwd, q, k, v)

    def grads(q, k, v):
        def loss(q, k, v):
            return jnp.sum(fwd(q, k, v).astype(jnp.float32) ** 2)

        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    _assert_mosaic_lowered(grads, q, k, v)


def test_packed_flash_lowers_for_tpu():
    """The round-4/5 packed kernel (segment ids, block skipping) has never met
    hardware; at minimum its Mosaic lowering must hold for fwd AND bwd."""
    q, k, v = _qkv()
    seg = _segments()

    def fwd(q, k, v, seg):
        return flash_attention(q, k, v, segment_ids=seg, causal=True, block_q=128, block_k=128)

    _assert_mosaic_lowered(fwd, q, k, v, seg)

    def grads(q, k, v, seg):
        def loss(q, k, v):
            return jnp.sum(fwd(q, k, v, seg).astype(jnp.float32) ** 2)

        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    _assert_mosaic_lowered(grads, q, k, v, seg)


def test_kv_lens_flash_lowers_for_tpu():
    """The padding-mask (kv_lens SMEM vector) variant — the exact shape family
    that broke Mosaic lowering in round 2."""
    q, k, v = _qkv(seq=128)
    kv_lens = jnp.asarray([100, 128], jnp.int32)

    def fwd(q, k, v, kv_lens):
        return flash_attention(q, k, v, kv_lens=kv_lens, block_q=128, block_k=128)

    _assert_mosaic_lowered(fwd, q, k, v, kv_lens)


def _abstract_bert_step(config, batch, seq, *, mu_dtype=None, **step_kw):
    """(train_step, abstract_state, abstract_batch) — eval_shape only, so full
    BERT-base programs export without materializing gigabytes of params."""
    from unionml_tpu.models import BertForSequenceClassification, create_train_state
    from unionml_tpu.models.training import make_classifier_train_step

    model = BertForSequenceClassification(config)
    abs_state = jax.eval_shape(
        lambda r: create_train_state(
            model,
            model.init({"params": r}, jnp.zeros((1, seq), jnp.int32)),
            learning_rate=2e-5, warmup_steps=10, total_steps=1000, mu_dtype=mu_dtype,
        ),
        jax.random.PRNGKey(0),
    )
    abs_batch = {
        "input_ids": jax.ShapeDtypeStruct((batch, seq), jnp.int32),
        "attention_mask": jax.ShapeDtypeStruct((batch, seq), jnp.int32),
        "labels": jax.ShapeDtypeStruct((batch,), jnp.int32),
    }
    step = make_classifier_train_step(
        input_signature=("input_ids", "attention_mask"), **step_kw
    )
    return step, abs_state, abs_batch


def test_headline_bert_train_step_lowers_for_tpu(monkeypatch):
    """The exact program the driver's bench times (BERT-base bf16, B=64, S=128,
    AdamW step) must lower for the TPU platform — a lowering regression here
    would turn the once-per-round hardware window into a 0.0 headline."""
    import sys

    from unionml_tpu.models import BertConfig
    from unionml_tpu.ops.tuning import pick_impl

    # the ops package re-exports the attention FUNCTION under the submodule's
    # name, so attribute-style imports resolve to the function — go via sys.modules
    attention_mod = sys.modules["unionml_tpu.ops.attention"]

    # trace-time dispatch must match HARDWARE dispatch: the model resolves
    # impl="auto" via on_tpu(), which is False on this CPU box — patched True so
    # the export contains whatever the tuning tables would run on the chip
    monkeypatch.setattr(attention_mod, "on_tpu", lambda: True)

    config = BertConfig.base(dtype=jnp.bfloat16)
    step, abs_state, abs_batch = _abstract_bert_step(config, batch=64, seq=128)
    exported = jax_export.export(step, platforms=["tpu"])(abs_state, abs_batch)
    mlir = exported.mlir_module()
    # the assertion tracks the measured dispatch verdict: with 'pallas' promoted
    # for the headline shape the export must carry the Mosaic kernel; with 'xla'
    # (the current measured verdict) its absence is the expected program — either
    # way a silent dispatch flip cannot pass unnoticed
    if pick_impl(128, 128, config.head_dim) == "pallas":
        assert "tpu_custom_call" in mlir, "pallas verdict but no Mosaic kernel exported"
    else:
        assert "tpu_custom_call" not in mlir, "xla verdict but a Mosaic kernel was exported"


def test_mfu_ladder_variants_lower_for_tpu(monkeypatch):
    """Every trainer variant nothing on the chip runs yet (remat, grad
    accumulation, bf16 adam moments, long-seq) must lower for the TPU platform,
    so that the first chip run of one is not spent on a lowering failure."""
    import sys

    from unionml_tpu.models import BertConfig

    # same hardware-dispatch patch as the headline test: without it the export
    # would trace the CPU attention branch, not the program the battery runs
    monkeypatch.setattr(sys.modules["unionml_tpu.ops.attention"], "on_tpu", lambda: True)

    variants = [
        dict(batch=256, seq=128, cfg=dict(remat=True)),
        dict(batch=512, seq=128, cfg=dict(remat=True), step=dict(grad_accum=4)),
        dict(batch=256, seq=128, cfg=dict(remat=True), mu=jnp.bfloat16),
        dict(batch=64, seq=512, cfg=dict(remat=True)),
    ]
    for spec in variants:
        config = BertConfig.base(dtype=jnp.bfloat16, **spec.get("cfg", {}))
        step, abs_state, abs_batch = _abstract_bert_step(
            config, batch=spec["batch"], seq=spec["seq"],
            mu_dtype=spec.get("mu"), **spec.get("step", {}),
        )
        exported = jax_export.export(step, platforms=["tpu"])(abs_state, abs_batch)
        assert exported.mlir_module_serialized, spec


def test_int8_decode_at_scale_lowers_for_tpu():
    """~1.3B-param int8-weight decode programs lower for TPU —
    exported from abstract (eval_shape) params/cache, so no memory is
    materialized. Covers BOTH phases the engine compiles: chunked prefill
    (cache write at position 0) and the cached single-token decode step
    (cache scatter/gather + per-token attention + dequant-fused matmuls)."""
    from unionml_tpu.models.gpt import GPTConfig, GPTLMHeadModel, init_cache
    from unionml_tpu.ops.quant import dequantize_tree, quantize_tree

    config = GPTConfig(
        vocab_size=50257, hidden_size=2048, num_layers=24, num_heads=16,
        max_position_embeddings=256, dropout=0.0, dtype=jnp.bfloat16,
    )
    model = GPTLMHeadModel(config)
    abs_vars = jax.eval_shape(
        lambda r: model.init({"params": r}, jnp.zeros((1, 8), jnp.int32), deterministic=True),
        jax.random.PRNGKey(0),
    )
    abs_qvars = jax.eval_shape(quantize_tree, abs_vars)
    abs_cache = jax.eval_shape(lambda: init_cache(config, 1, 128))
    abs_position = jax.ShapeDtypeStruct((), jnp.int32)

    def prefill(qvars, ids, cache):
        return model.apply(
            dequantize_tree(qvars), ids, cache=cache, position=0, deterministic=True
        )

    exported = jax_export.export(jax.jit(prefill), platforms=["tpu"])(
        abs_qvars, jax.ShapeDtypeStruct((1, 8), jnp.int32), abs_cache
    )
    assert exported.mlir_module_serialized

    def decode_step(qvars, token, cache, position):
        return model.apply(
            dequantize_tree(qvars), token, cache=cache, position=position,
            deterministic=True,
        )

    exported = jax_export.export(jax.jit(decode_step), platforms=["tpu"])(
        abs_qvars, jax.ShapeDtypeStruct((1, 1), jnp.int32), abs_cache, abs_position
    )
    assert exported.mlir_module_serialized


def test_sharded_parallelism_programs_lower_for_tpu():
    """The multi-chip shard_map programs (ring SP, pipeline, a2a MoE) must lower
    for the TPU platform — the CPU dryrun proves numerics, this proves the same
    collectives (ppermute / all_to_all / psum) lower for the real target."""
    from unionml_tpu.parallel import make_mesh
    from unionml_tpu.parallel.ep import moe_apply_a2a
    from unionml_tpu.parallel.pp import pipeline_apply
    from unionml_tpu.parallel.ring import ring_attention
    from unionml_tpu.parallel.ulysses import ulysses_attention

    rng = np.random.default_rng(0)

    ep_mesh = make_mesh({"data": 2, "expert": 4})
    eW = jnp.asarray(rng.normal(size=(8, 16, 16)) * 0.3, jnp.float32)
    tokens = jnp.asarray(rng.normal(size=(32, 16)), jnp.float32)
    gates = jax.nn.softmax(jnp.asarray(rng.normal(size=(32, 8)), jnp.float32), axis=-1)
    a2a = jax.jit(
        lambda w, t, g: moe_apply_a2a(
            lambda we, te: te @ we, w, t, g, ep_mesh, k=2, capacity_factor=4.0
        )
    )
    assert jax_export.export(a2a, platforms=["tpu"])(eW, tokens, gates).mlir_module_serialized

    sp_mesh = make_mesh({"data": 2, "sequence": 4})
    q = jnp.asarray(rng.normal(size=(2, 4, 32, 16)), jnp.float32)  # heads % sequence == 0 (ulysses)
    for sp_fn in (
        lambda q, k, v: ring_attention(q, k, v, sp_mesh, causal=True),
        lambda q, k, v: ulysses_attention(q, k, v, sp_mesh, causal=True),
    ):
        assert jax_export.export(jax.jit(sp_fn), platforms=["tpu"])(q, q, q).mlir_module_serialized

    pp_mesh = make_mesh({"data": 2, "stage": 4})
    stage_w = jnp.asarray(rng.normal(size=(4, 16, 16)) * 0.2, jnp.float32)
    pp_x = jnp.asarray(rng.normal(size=(8, 16)), jnp.float32)
    pp = jax.jit(
        lambda w, x: pipeline_apply(
            lambda w, h: jax.nn.relu(h @ w), w, x, pp_mesh, num_microbatches=4
        )
    )
    assert jax_export.export(pp, platforms=["tpu"])(stage_w, pp_x).mlir_module_serialized


def test_tuned_block_tables_lower_for_tpu():
    """Every committed TUNED_BLOCKS / PACKED_TUNED_BLOCKS entry must stay
    Mosaic-lowerable: a tuning overlay promoting an unlowering config would
    break the next hardware run. Shapes honor seq_q != seq_k keys, and the
    packed table (the kernel that has never met hardware) runs the
    segment-ids kernel."""
    from unionml_tpu.ops.tuning import PACKED_TUNED_BLOCKS, TUNED_BLOCKS

    for (seq_q, seq_k, head_dim), (block_q, block_k) in sorted(TUNED_BLOCKS.items()):
        q, k, v = _qkv(batch=1, heads=2, seq=seq_q, dim=head_dim, seq_kv=seq_k)

        def fwd(q, k, v, bq=block_q, bk=block_k):
            return flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk)

        _assert_mosaic_lowered(fwd, q, k, v)

    for (seq_q, seq_k, head_dim), (block_q, block_k) in sorted(PACKED_TUNED_BLOCKS.items()):
        q, k, v = _qkv(batch=1, heads=2, seq=seq_q, dim=head_dim, seq_kv=seq_k)
        seg = _segments(batch=1, seq=max(seq_q, seq_k))

        def packed_fwd(q, k, v, seg, bq=block_q, bk=block_k):
            return flash_attention(
                q, k, v, segment_ids=seg, causal=True, block_q=bq, block_k=bk
            )

        _assert_mosaic_lowered(packed_fwd, q, k, v, seg)


# ------------------------------------------------------------ paged attention

#: (heads, head_dim, block_size, table_width, batch): GPT-2 small as the server
#: pages it (max_len 1024, 16-token blocks, 8 slots), GPT-2 medium as the
#: benchmark's serving cell does (48 slots) and the unit tests' pool
PAGED_SHAPES = {
    "gpt2_small": (12, 64, 16, 65, 8),
    "gpt2_medium": (16, 64, 16, 65, 48),
    "tiny": (2, 16, 4, 4, 3),
}
REAL_SHAPES = ["gpt2_small", "gpt2_medium"]


def _paged_case(shape, pool, mode):
    """Abstract operands of one paged call: ``pool`` bf16 (the one joined leaf,
    ``[key | value]`` rows, no value operand) | int8 (code leaves and scales),
    ``mode`` decode (S=1) | chunk (batch 1, S=64 or 6) | verify (identity table
    over batch*width local blocks, int8 codes carried as f32)."""
    heads, head_dim, block_size, width, batch = PAGED_SHAPES[shape]
    compute = jnp.bfloat16 if shape in REAL_SHAPES else jnp.float32
    blocks, seq, code_dtype = batch * (width - 1) + 1, 1, jnp.int8
    if mode == "chunk":
        batch, seq = 1, 64 if shape in REAL_SHAPES else 6
    elif mode == "verify":
        blocks, code_dtype = batch * width, jnp.float32
    quantized = pool == "int8"
    if quantized:
        leaf = jax.ShapeDtypeStruct((blocks, heads, block_size, head_dim), code_dtype)
    else:
        leaf = jax.ShapeDtypeStruct((blocks, heads, block_size, 2 * head_dim), compute)
    args = [
        jax.ShapeDtypeStruct((batch, heads, seq, head_dim), compute), leaf, leaf if quantized else None,
        jax.ShapeDtypeStruct((batch, width), jnp.int32),
        jax.ShapeDtypeStruct((batch,), jnp.int32),
    ]
    if quantized:
        args += [jax.ShapeDtypeStruct((blocks, heads, 1, 1), jnp.float32)] * 2
    return compute, args


def _paged_fn(compute, mesh=None):
    from unionml_tpu.ops.paged_attention import paged_attention

    def fn(q, k, v, table, base, k_scale=None, v_scale=None):
        return paged_attention(
            q, k, v, table, base, k_scale=k_scale, v_scale=v_scale,
            out_dtype=compute, impl="pallas", mesh=mesh,
        )

    return fn


@pytest.fixture
def as_on_tpu(monkeypatch):
    """``impl="pallas"`` refuses a non-TPU backend; these tests build the TPU
    program on the CPU box, so the dispatcher is told it is on the chip."""
    import importlib

    # by module path: the ops package re-exports same-named FUNCTIONS over its submodules
    module = importlib.import_module("unionml_tpu.ops.paged_attention")
    monkeypatch.setattr(module, "on_tpu", lambda: True)


@pytest.mark.parametrize("mode", ["decode", "chunk", "verify"])
@pytest.mark.parametrize("pool", ["bf16", "int8"])
@pytest.mark.parametrize("shape", sorted(PAGED_SHAPES))
def test_paged_attention_lowers_for_tpu(as_on_tpu, shape, pool, mode):
    """The TPU-default decode kernel, every variant the engine traces. The int8
    variants are the regression gate for the (1, heads) scale block spec the
    Mosaic lowering refused."""
    compute, args = _paged_case(shape, pool, mode)
    _assert_mosaic_lowered(_paged_fn(compute), *args)


@pytest.mark.parametrize("pool", ["bf16", "int8"])
@pytest.mark.parametrize("shape", REAL_SHAPES)
def test_paged_attention_lowers_under_tensor_mesh(as_on_tpu, shape, pool):
    """The serving mesh's form of the call: the kernel shard_mapped over
    ``tensor`` (heads local) still lowers to a Mosaic call."""
    from unionml_tpu.parallel import make_mesh

    mesh = make_mesh({"data": 1, "tensor": 4}, devices=jax.devices()[:4])
    compute, args = _paged_case(shape, pool, "decode")
    _assert_mosaic_lowered(_paged_fn(compute, mesh=mesh), *args)


@pytest.fixture(scope="module")
def v5e_host():
    """The four devices of a v5e 2x2 host as libtpu describes them — no chip
    attached — so ``lower().compile()`` runs the real Mosaic and XLA:TPU
    compilers on this CPU box. Skips where libtpu cannot describe one."""
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as env:
        # libtpu reads these once, when it loads: no metadata server, no workers
        env.setenv("TPU_SKIP_MDS_QUERY", "1")
        env.setenv("TPU_ACCELERATOR_TYPE", "v5litepod-4")
        env.setenv("TPU_WORKER_HOSTNAMES", "localhost")
        try:
            return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices
        except Exception as exc:  # no libtpu / refused topology: environment, not a defect
            pytest.skip(f"libtpu cannot describe a v5e host here: {exc}")


@pytest.mark.parametrize("mode", ["decode", "chunk", "verify"])
@pytest.mark.parametrize("pool", ["bf16", "int8"])
@pytest.mark.parametrize("shape", REAL_SHAPES)
def test_paged_attention_compiles_under_mosaic(as_on_tpu, v5e_host, shape, pool, mode):
    """Lowering is the first gate; this is the second: Mosaic compiles every
    GPT-2 small and medium variant to machine code for a v5e."""
    from jax.sharding import SingleDeviceSharding

    compute, args = _paged_case(shape, pool, mode)
    on_chip = SingleDeviceSharding(v5e_host[0])
    args = [a and jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=on_chip) for a in args]
    text = jax.jit(_paged_fn(compute)).lower(*args).compile().as_text()
    # whichever walk the pool's leaves take (copies over the joined leaf, the
    # BlockSpec grid over int8 codes), the call's first operand is the table
    batch, width = args[3].shape
    assert f'custom_call_target="tpu_custom_call", operand_layout_constraints={{s32[{batch},{width}]' in text


@pytest.fixture(scope="module")
def cell_engine():
    """A one-layer engine with the closed serving cell's cache shapes (48 slots
    of 1024 positions, 16 heads of 64, 16-token blocks) over a pool of a few
    blocks: its programs are lowered for the cell's 3073."""
    from unionml_tpu.models.gpt import GPTConfig, GPTLMHeadModel
    from unionml_tpu.serving.continuous import DecodeEngine

    cfg = GPTConfig(
        vocab_size=512, hidden_size=1024, num_layers=1, num_heads=16,
        max_position_embeddings=1024, dropout=0.0, dtype=jnp.bfloat16, paged_attn_impl="pallas",
    )
    model = GPTLMHeadModel(cfg)
    variables = model.init({"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 8), jnp.int32))
    return DecodeEngine(
        model, variables, num_slots=48, max_len=1024, prefix_block_size=16, pool_blocks=66
    )


@pytest.fixture(scope="module")
def latent_engine():
    """A one-layer engine with the sparse-decoder cell's cache shapes (32 slots
    of 8192 positions, 128-token blocks, one 640-wide leaf a layer: 512 latent
    + 64 rotary + 64 of padding) at the published attention widths; the feed-
    forward and the vocabulary are small, the pool a few blocks: its programs
    are lowered for the cell's 2049."""
    from unionml_tpu.models.latent_moe import LatentMoEConfig, LatentMoELMHeadModel, init_params
    from unionml_tpu.serving.continuous import DecodeEngine

    cfg = LatentMoEConfig(
        vocab_size=512, num_layers=1, first_k_dense_replace=1, intermediate_size=256,
        max_position_embeddings=8192, paged_attn_impl="pallas",
    )
    return DecodeEngine(
        LatentMoELMHeadModel(cfg), init_params(cfg), num_slots=32, max_len=8192,
        prefix_block_size=128, pool_blocks=66, prefill_buckets=(256, 512, 1024),
        prefill_chunk=1024, prefill_batch=1,
    )


@pytest.mark.parametrize("program,engine,blocks", [
    ("decode_step", "cell_engine", 3073),
    ("prefill_wave", "cell_engine", 3073),
    ("paged_chunk", "cell_engine", 3073),
    ("prefill_wave", "latent_engine", 32 * 64 + 1),
])
def test_pool_writers_compile_without_pool_copies(as_on_tpu, v5e_host, request, program, engine, blocks):
    """The engine's programs that write a full-precision pool, compiled for a
    v5e at the serving cells' shapes (one layer) with the pool donated. At the
    closed cell's 3073 blocks: the decode step (48 appends, then the Mosaic
    call), the fused admission wave (4 rows of 128: the table rows, the bucket
    prefill, its workspace scattered whole blocks at a time, the lengths, the
    logits and the slot mirrors in one program that donates everything but the
    weights) and a 64-token chunk through a table row. At the latent layout's
    2049 blocks of 128 tokens and 640-wide rows: the sparse cell's wave, one
    row of 512. None may hold a ``copy`` the size of the pool. The layout this
    guards: one leaf a layer whose rows are whole 128-lane tiles, written by
    scatters whose indexed axes lead. With two 64-wide leaves and ``.at[dst,
    :, off, :]`` XLA re-laid the pool out around every scatter and call: 6, 4
    and 6 such copies a layer in these programs, 86% of the closed cell's
    device time. And a program that holds the donated pool together with new
    work (here the whole prefill) is where XLA would re-lay it out again."""
    import re

    from jax.sharding import SingleDeviceSharding

    from unionml_tpu.serving.continuous import _WAVE_SCALARS

    e = request.getfixturevalue(engine)
    small = e.pool_blocks
    on_chip = SingleDeviceSharding(v5e_host[0])

    def abstract(tree):
        def leaf(x):
            shape = (blocks,) + x.shape[1:] if x.ndim == 4 and x.shape[0] == small else x.shape
            return jax.ShapeDtypeStruct(shape, x.dtype, sharding=on_chip)

        return jax.tree_util.tree_map(leaf, tree)

    def on(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=on_chip)

    if program == "decode_step":
        lowered = e._make_step(1, False).lower(*abstract((
            e._variables, e._pool, e._tables, e._last_logits, e._lens, e._active_dev,
            e._remaining_dev, e._key, e._temp_dev, e._top_k_dev, e._top_p_dev,
        )))
    elif program == "prefill_wave":
        rows, bucket = (4, 128) if engine == "cell_engine" else (1, 512)
        lowered = e._prefill_wave_fn.lower(*abstract((
            e._variables, e._pool, e._tables, e._lens, e._last_logits, e._active_dev,
            e._remaining_dev, e._temp_dev, e._top_k_dev, e._top_p_dev,
        )), on((rows, bucket + _WAVE_SCALARS + e._table_width)))
    else:
        lowered = e._paged_chunk_fn.lower(
            abstract(e._variables), on((1, 64)), *abstract((e._pool, e._tables)), on(()), on(()), on(()),
        )
    text = lowered.compile().as_text()
    assert f"[{blocks}," in text  # the pool is in the program at the cell's size
    copies = [
        line.strip()[:120] for line in text.splitlines()
        if " copy(" in line and re.search(rf"= \S+\[{blocks},", line)
    ]
    assert not copies, f"{len(copies)} pool-sized copies in {program}:\n" + "\n".join(copies)


@pytest.mark.parametrize("mode,batch,seq", [("decode", 32, 1), ("chunk", 1, 1024)])
def test_latent_paged_attention_compiles_under_mosaic(as_on_tpu, v5e_host, mode, batch, seq):
    """The sparse-decoder cell's call: 32 query heads over one key head whose
    640-wide rows (512 latent + 64 rotary + 64 of padding) are the values too,
    128-token blocks, 32 slots of 8192 positions. Decode takes the 32 heads'
    rows in one step and three of the leaf's 160 KB blocks a tile; a 1024-token
    chunk splits its 32 x 1024 rows and takes a block a tile. The block table
    is the Mosaic call's first operand and the positions its second, as the
    benchmark's trace readers find the decode kernel
    (``perfbench/configs/xing4-29b-a4b-serve.json``: ``custom-call(s32[32,65]``):
    a dynamic grid extent would stand in front of them."""
    from jax.sharding import SingleDeviceSharding

    from unionml_tpu.ops.paged_attention import _tiling, paged_attention

    heads, row, block_size, width = 32, 640, 128, 8192 // 128 + 1
    on_chip = SingleDeviceSharding(v5e_host[0])
    args = [
        jax.ShapeDtypeStruct((batch, heads, seq, row), jnp.bfloat16, sharding=on_chip),
        jax.ShapeDtypeStruct((32 * (width - 1) + 1, 1, block_size, row), jnp.bfloat16, sharding=on_chip),
        jax.ShapeDtypeStruct((batch, width), jnp.int32, sharding=on_chip),
        jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=on_chip),
    ]

    def fn(q, pool, table, base):
        return paged_attention(q, pool, None, table, base, impl="pallas", sm_scale=0.1447)

    text = jax.jit(fn).lower(*args).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    operands = f"operand_layout_constraints={{s32[{batch},{width}]{{1,0}}, s32[{batch}]{{0}}, bf16["
    assert operands in text
    want = (1, 32, 3) if mode == "decode" else (1, 512, 1)
    assert _tiling(1, heads * seq, seq, block_size, row, width, 2, False, True) == want


@pytest.mark.parametrize("pool", ["bf16", "int8"])
@pytest.mark.parametrize("shape", REAL_SHAPES)
def test_paged_attention_partitions_over_tensor_mesh(as_on_tpu, v5e_host, shape, pool):
    """Under the serving mesh the kernel sits inside a multi-device jit on a
    head-sharded pool. Bare, the partitioner refuses it ("Mosaic kernels cannot
    be automatically partitioned"); under ``mesh=`` it is shard_mapped with
    heads local, and the compiled four-chip program moves no pool bytes: no
    all-gather anywhere in it."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.asarray(v5e_host).reshape(1, 4), ("data", "tensor"))
    by_head = NamedSharding(mesh, P(None, "tensor", None, None))
    compute, args = _paged_case(shape, pool, "decode")
    args = [
        a and jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=by_head if len(a.shape) == 4 else NamedSharding(mesh, P())
        )
        for a in args
    ]
    with pytest.raises(NotImplementedError, match="cannot be automatically partitioned"):
        jax.jit(_paged_fn(compute)).lower(*args).compile()
    compiled = jax.jit(_paged_fn(compute, mesh=mesh), out_shardings=by_head).lower(*args).compile()
    assert "all-gather" not in compiled.as_text()


# ------------------------------------------- the hybrid decoder's three kernels


@pytest.mark.parametrize("call", ["full_cache", "window_ring", "ssm_step", "ssm_scan"])
def test_hybrid_decoder_kernels_compile_under_mosaic(as_on_tpu, v5e_host, monkeypatch, call):
    """The hybrid cell's kernels at its sizes (64 slots, blocks of 128
    tokens, rows of a key group 256 lanes wide): the full layer's and the cross
    layers' call (40 zero-padded query heads over 10 key groups, a table of 33
    columns), the window layers' (the rotated ring of 5 blocks, a window of
    512), the state update (16 x 5120 float32 a slot, in place) and a
    1,024-token bucket's scan (64 tokens x 512 channels a grid step). The
    attention calls' first operand is their table, which is how the benchmark's
    readers tell the two apart (``perfbench/configs/phi4-mini-flash-serve.json``)."""
    import importlib

    from jax.sharding import SingleDeviceSharding

    from unionml_tpu.ops.paged_attention import paged_attention

    ssm = importlib.import_module("unionml_tpu.ops.ssm")
    monkeypatch.setattr(ssm, "on_tpu", lambda: True)
    on_chip = SingleDeviceSharding(v5e_host[0])
    on = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=on_chip)
    slots, groups, block, row, channels, states = 64, 10, 128, 256, 5120, 16
    if call == "ssm_scan":
        seq = 1024
        args = [
            on((1, seq, channels), jnp.bfloat16), on((1, seq, channels), jnp.float32), on((states, channels), jnp.float32),
            on((1, seq, states), jnp.bfloat16), on((1, seq, states), jnp.bfloat16), on((channels,), jnp.float32),
            on((1, states, channels), jnp.float32), on((1,), jnp.int32),
        ]
        fn = lambda u, delta, a, b, c, d, state, valid: ssm.selective_scan(u, delta, a, b, c, d, state, valid, impl="pallas")
        text = jax.jit(fn).lower(*args).compile().as_text()
        assert text.count('custom_call_target="tpu_custom_call"') == 1 and "%ssm_scan" in text
        return
    if call == "ssm_step":
        args = [
            on((slots, channels), jnp.bfloat16), on((slots, channels), jnp.float32), on((states, channels), jnp.float32),
            on((slots, states), jnp.bfloat16), on((slots, states), jnp.bfloat16), on((channels,), jnp.float32),
            on((slots, states, channels), jnp.float32), on((slots,), jnp.bool_),
        ]
        fn = lambda u, delta, a, b, c, d, state, live: ssm.selective_step(
            u, delta, a, b, c, d, state, live=live, impl="pallas")
        text = jax.jit(fn, donate_argnums=(6,)).lower(*args).compile().as_text()
        assert text.count('custom_call_target="tpu_custom_call"') == 1 and "%ssm_step" in text
        assert f"f32[{slots},{states},{channels}]" in text
        # in place: no copy of the state around the call
        assert not [line for line in text.splitlines() if " copy(" in line and f"f32[{slots},{states},{channels}]" in line]
        return
    width, blocks, window = (33, slots * 32 + 1, None) if call == "full_cache" else (5, slots * 5 + 1, 512)
    args = [
        on((slots, 40, 1, row // 2), jnp.bfloat16), on((blocks, groups, block, row), jnp.bfloat16),
        on((slots, width), jnp.int32), on((slots,), jnp.int32),
    ]
    fn = lambda q, pool, table, base: paged_attention(
        q, pool, None, table, base, impl="pallas", sm_scale=0.125, window=window, out_dtype=jnp.float32)
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert f"operand_layout_constraints={{s32[{slots},{width}]{{1,0}}, s32[{slots}]{{0}}, bf16[" in text

"""GPT decoder tests: cached generation exactness, trainability, sampling."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from unionml_tpu.models.gpt import (
    GPTConfig,
    GPTLMHeadModel,
    generate,
    init_params,
    lm_loss,
)


@pytest.fixture(scope="module")
def tiny(gpt_tiny_session):
    # session-scoped (shared with the serving/engine suites): one init for the run
    return gpt_tiny_session


def test_forward_shapes(tiny):
    cfg, model, variables = tiny
    logits = model.apply(variables, jnp.ones((2, 8), dtype=jnp.int32), deterministic=True)
    assert logits.shape == (2, 8, cfg.vocab_size)
    assert logits.dtype == jnp.float32


def test_cached_generation_matches_full_recompute(tiny):
    cfg, model, variables = tiny
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(2, 5)), dtype=jnp.int32)

    ids = prompt
    # each reference iteration compiles a fresh (longer) full forward; 4 steps
    # prove cache parity at a third of the compile bill 6 did
    for _ in range(4):
        logits = model.apply(variables, ids, deterministic=True)
        nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        ids = jnp.concatenate([ids, nxt[:, None]], axis=1)

    out = generate(model, variables, prompt, max_new_tokens=4, max_len=16)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ids))

    jitted = jax.jit(lambda p: generate(model, variables, p, max_new_tokens=4, max_len=16))
    np.testing.assert_array_equal(np.asarray(jitted(prompt)), np.asarray(ids))


def test_temperature_sampling_stays_in_vocab(tiny):
    cfg, model, variables = tiny
    prompt = jnp.ones((1, 3), dtype=jnp.int32)
    out = generate(
        model, variables, prompt, max_new_tokens=5, temperature=1.0, rng=jax.random.PRNGKey(7), max_len=16
    )
    assert out.shape == (1, 8)
    assert bool(jnp.all((out >= 0) & (out < cfg.vocab_size)))


def test_lm_training_reduces_loss(tiny):
    cfg, model, variables = tiny
    rng = np.random.default_rng(1)
    # a memorizable repeating sequence
    ids = jnp.asarray(np.tile(rng.integers(0, cfg.vocab_size, size=(1, 4)), (4, 4)), dtype=jnp.int32)
    params = variables["params"]
    tx = optax.adam(1e-3)
    opt_state = tx.init(params)

    @jax.jit
    def step(params, opt_state):
        def loss_fn(p):
            logits = model.apply({"params": p}, ids, deterministic=True)
            return lm_loss(logits, ids)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state)
        return optax.apply_updates(params, updates), opt_state, loss

    losses = []
    for _ in range(25):
        params, opt_state, loss = step(params, opt_state)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.5, losses[::6]


def test_lm_loss_masks_padding(tiny):
    cfg, model, variables = tiny
    ids = jnp.asarray([[5, 6, 7, 0, 0]], dtype=jnp.int32)
    mask = jnp.asarray([[1, 1, 1, 0, 0]], dtype=jnp.int32)
    logits = model.apply(variables, ids, deterministic=True)
    masked = lm_loss(logits, ids, mask)
    unmasked_prefix = lm_loss(logits[:, :3], ids[:, :3])
    np.testing.assert_allclose(float(masked), float(unmasked_prefix), rtol=1e-5)


def test_generate_rejects_out_of_range_lengths(tiny):
    cfg, model, variables = tiny
    prompt = jnp.ones((1, 5), dtype=jnp.int32)
    with pytest.raises(ValueError, match="exceeds max_len"):
        generate(model, variables, prompt, max_new_tokens=6, max_len=8)
    with pytest.raises(ValueError, match="max_position_embeddings"):
        generate(model, variables, prompt, max_new_tokens=cfg.max_position_embeddings + 10)


def test_cache_dtype_follows_config():
    from unionml_tpu.models.gpt import init_cache

    bf16_cfg = GPTConfig.tiny()  # default bfloat16 compute
    cache = init_cache(bf16_cfg, batch=1, max_len=8)
    assert cache["layer_0"]["k"].dtype == jnp.bfloat16
    f32_cache = init_cache(bf16_cfg, batch=1, max_len=8, dtype=jnp.float32)
    assert f32_cache["layer_0"]["k"].dtype == jnp.float32


def test_package_level_gpt_exports():
    from unionml_tpu.models import gpt_generate, gpt_lm_loss, init_gpt_cache, init_gpt_params

    cfg = GPTConfig.tiny(dtype=jnp.float32)
    variables = init_gpt_params(cfg, seq_len=8)
    assert "wte" in variables["params"]
    assert gpt_generate is generate and gpt_lm_loss is lm_loss


def test_logits_are_f32_under_bf16_config():
    """The tied head must emit genuine f32 logits even with bf16 compute."""
    cfg = GPTConfig.tiny(dropout=0.0)  # default bfloat16
    model = GPTLMHeadModel(cfg)
    variables = init_params(cfg, seq_len=8)
    logits = model.apply(variables, jnp.ones((1, 8), dtype=jnp.int32), deterministic=True)
    assert logits.dtype == jnp.float32


def test_sparse_gpt_forward_and_aux_losses():
    """moe_every swaps dense MLPs for routed experts; router losses sow."""
    import numpy as np

    from unionml_tpu.models import collect_aux_losses
    from unionml_tpu.models.gpt import GPTConfig, GPTLMHeadModel, init_params

    config = GPTConfig.tiny(moe_every=2, num_experts=4, moe_k=2, dropout=0.0)
    model = GPTLMHeadModel(config)
    variables = init_params(config, seq_len=16)
    ids = jnp.asarray(np.random.default_rng(0).integers(0, config.vocab_size, (2, 16)))

    logits, state = model.apply(variables, ids, mutable=["intermediates"])
    assert logits.shape == (2, 16, config.vocab_size)
    aux = collect_aux_losses(state["intermediates"])
    assert float(aux) > 0.0

    # layer_1 (the 2nd block) carries expert params; layer_0 stays dense
    params = variables["params"]
    assert "moe_mlp" in params["layer_1"]
    assert "moe_mlp" not in params["layer_0"] and "mlp_up" in params["layer_0"]


def test_sparse_gpt_generates_with_cache():
    """KV-cache decoding works through MoE blocks (per-token routing)."""
    import numpy as np

    from unionml_tpu.models.gpt import GPTConfig, GPTLMHeadModel, generate, init_params

    # f32 + 'xla' pinned like the dense exactness test: the cached path mixes
    # prefill attention() with decode xla_attention(), and bf16 rounding could
    # flip near-tied argmaxes under impl='auto' on TPU
    config = GPTConfig.tiny(
        moe_every=2, num_experts=4, moe_k=2, dropout=0.0,
        dtype=jnp.float32, attention_impl="xla",
    )
    model = GPTLMHeadModel(config)
    variables = init_params(config, seq_len=16)
    prompt = jnp.asarray(np.random.default_rng(1).integers(0, config.vocab_size, (2, 5)))
    out = generate(model, variables, prompt, max_new_tokens=6)
    assert out.shape == (2, 11)
    # cached decode must match the uncached full forward argmax continuation
    full_logits = model.apply(variables, out)
    np.testing.assert_array_equal(
        np.asarray(jnp.argmax(full_logits[:, 4:-1], axis=-1)), np.asarray(out[:, 5:])
    )


def test_gpt_param_shardings_cover_tree_and_train_sharded():
    """Megatron-style GPT shardings: every 2D+ kernel gets a tensor split, and a
    sharded train step runs on a data x tensor mesh (sparse blocks included)."""
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec

    from unionml_tpu.models.gpt import GPTConfig, GPTLMHeadModel, init_params, lm_loss, param_shardings
    from unionml_tpu.parallel import make_mesh

    config = GPTConfig.tiny(moe_every=2, num_experts=4, dropout=0.0, dtype=jnp.float32,
                            attention_impl="xla")
    variables = init_params(config, seq_len=16)
    specs = param_shardings(variables["params"], ("data", "tensor"))

    flat = jax.tree_util.tree_flatten_with_path(
        specs, is_leaf=lambda x: isinstance(x, PartitionSpec)
    )[0]
    sharded_kernels = 0
    for path, spec in flat:
        assert isinstance(spec, PartitionSpec)
        if "tensor" in str(spec):
            sharded_kernels += 1
    # 4 tensor-sharded kernels per layer (qkv, attn_out, and both MLP/expert mats)
    assert sharded_kernels >= 4 * config.num_layers

    mesh = make_mesh({"data": 4, "tensor": 2})
    sharding_tree = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs, is_leaf=lambda x: isinstance(x, PartitionSpec)
    )
    params = jax.device_put(variables["params"], sharding_tree)
    model = GPTLMHeadModel(config)
    ids = jnp.asarray(np.random.default_rng(0).integers(0, config.vocab_size, (8, 16)))

    @jax.jit
    def loss_fn(params, ids):
        logits = model.apply({"params": params}, ids)
        return lm_loss(logits, ids)

    loss, grads = jax.value_and_grad(loss_fn)(params, ids)
    assert float(loss) > 0
    # gradients inherit the parameter layouts
    qkv_grad = grads["layer_0"]["qkv"]["kernel"]
    assert "tensor" in str(qkv_grad.sharding.spec)


def test_gpt_hf_weight_parity():
    """Imported HF GPT-2 weights must reproduce transformers' logits."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    import numpy as np

    from unionml_tpu.models.gpt import GPTConfig, GPTLMHeadModel, import_hf_weights

    hf_config = transformers.GPT2Config(
        vocab_size=128, n_positions=64, n_embd=32, n_layer=2, n_head=4,
        resid_pdrop=0.0, embd_pdrop=0.0, attn_pdrop=0.0,
    )
    torch.manual_seed(0)
    hf_model = transformers.GPT2LMHeadModel(hf_config).eval()

    config = GPTConfig(
        vocab_size=128, hidden_size=32, num_layers=2, num_heads=4,
        max_position_embeddings=64, dropout=0.0, dtype=jnp.float32, attention_impl="xla",
    )
    variables = import_hf_weights(hf_model.state_dict(), config)

    ids = np.random.default_rng(0).integers(0, 128, size=(2, 16))
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(ids)).logits.numpy()
    ours = np.asarray(GPTLMHeadModel(config).apply(
        jax.tree_util.tree_map(jnp.asarray, variables), jnp.asarray(ids)
    ))
    np.testing.assert_allclose(ours, hf_logits, atol=2e-4)


def test_ragged_prompt_batched_generation_matches_single():
    """Left-padded ragged prompts in one batch decode exactly as each would alone."""
    import numpy as np

    from unionml_tpu.models.gpt import GPTConfig, GPTLMHeadModel, generate, init_params

    config = GPTConfig.tiny(dropout=0.0, dtype=jnp.float32, attention_impl="xla")
    model = GPTLMHeadModel(config)
    variables = init_params(config, seq_len=16)
    rng = np.random.default_rng(3)

    short = rng.integers(1, config.vocab_size, 3)
    long = rng.integers(1, config.vocab_size, 7)

    # singles (no padding)
    out_short = np.asarray(generate(model, variables, jnp.asarray(short[None]), max_new_tokens=5))
    out_long = np.asarray(generate(model, variables, jnp.asarray(long[None]), max_new_tokens=5))

    # one batch, left-padded to length 7
    padded = np.zeros((2, 7), dtype=np.int64)
    mask = np.zeros((2, 7), dtype=np.int32)
    padded[0, 4:] = short
    mask[0, 4:] = 1
    padded[1] = long
    mask[1] = 1
    out = np.asarray(
        generate(model, variables, jnp.asarray(padded), max_new_tokens=5,
                 prompt_mask=jnp.asarray(mask))
    )
    # row 0's real content: positions 4.. of the padded row + the 5 new tokens
    np.testing.assert_array_equal(out[0, 4:], out_short[0])
    np.testing.assert_array_equal(out[1], out_long[0])


def test_full_forward_with_pad_offsets_matches_unpadded():
    """cache=None forward: logits at real positions equal the unpadded forward."""
    import numpy as np

    from unionml_tpu.models.gpt import GPTConfig, GPTLMHeadModel, init_params

    config = GPTConfig.tiny(dropout=0.0, dtype=jnp.float32, attention_impl="xla")
    model = GPTLMHeadModel(config)
    variables = init_params(config, seq_len=16)
    rng = np.random.default_rng(4)

    ids = rng.integers(1, config.vocab_size, 6)
    plain = np.asarray(model.apply(variables, jnp.asarray(ids[None])))

    padded = np.zeros((1, 9), dtype=np.int64)
    padded[0, 3:] = ids
    out = np.asarray(
        model.apply(variables, jnp.asarray(padded), pad_offsets=jnp.asarray([3]))
    )
    np.testing.assert_allclose(out[0, 3:], plain[0], atol=1e-4)


@pytest.mark.parametrize("sp_impl", ["ring", "ulysses"])
def test_gpt_sequence_parallel_training_matches_xla(sp_impl):
    """Long-context GPT training: ring/Ulysses attention over a sequence mesh must
    reproduce the dense causal forward AND its gradients."""
    import numpy as np

    from unionml_tpu.models.gpt import GPTConfig, GPTLMHeadModel, init_params, lm_loss
    from unionml_tpu.parallel import make_mesh

    # 2 sequence shards: wiring-level parity only needs >1 shard here — the ring
    # collective's multi-hop coverage (4 shards, padding, causality) lives in the
    # op-level tests (test_parallel.py), and each extra shard lengthens the
    # unrolled ppermute chain the grad compile pays for. One layer for the same
    # reason: the property (sp forward+grad parity vs dense) is per-layer.
    mesh = make_mesh({"data": 4, "sequence": 2})
    base = dict(dropout=0.0, dtype=jnp.float32, num_layers=1)
    sp_config = GPTConfig.tiny(attention_impl=sp_impl, sp_mesh=mesh, **base)
    xla_config = GPTConfig.tiny(attention_impl="xla", **base)

    variables = init_params(xla_config, seq_len=32)
    ids = jnp.asarray(np.random.default_rng(0).integers(0, xla_config.vocab_size, (4, 32)))

    def logits_and_grads(config):
        # one traced program for forward AND backward: the sp grad's unrolled
        # ppermute chain dominates this test's compile bill, so it must not be
        # compiled twice (a separate apply + grad pair measured ~2x slower)
        def fn(params):
            logits = GPTLMHeadModel(config).apply({"params": params}, ids)
            return lm_loss(logits, ids), logits

        grads, logits = jax.grad(fn, has_aux=True)(variables["params"])
        return logits, grads

    sp_logits, g_sp = logits_and_grads(sp_config)
    xla_logits, g_xla = logits_and_grads(xla_config)
    np.testing.assert_allclose(np.asarray(sp_logits), np.asarray(xla_logits), atol=2e-4)
    for a, b in zip(jax.tree_util.tree_leaves(g_sp), jax.tree_util.tree_leaves(g_xla)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)


def test_gpt_sp_requires_mesh_and_generates_via_fallback():
    import numpy as np

    from unionml_tpu.models.gpt import GPTConfig, GPTLMHeadModel, generate, init_params
    from unionml_tpu.parallel import make_mesh

    config = GPTConfig.tiny(attention_impl="ring", dropout=0.0, dtype=jnp.float32)
    model = GPTLMHeadModel(config)
    variables = init_params(GPTConfig.tiny(dropout=0.0, dtype=jnp.float32), seq_len=16)
    with pytest.raises(ValueError, match="requires a sequence-parallel mesh"):
        model.apply(variables, jnp.ones((2, 16), dtype=jnp.int32))

    # generation works on a ring config: decode paths use per-token attention
    mesh = make_mesh({"data": 2, "sequence": 4})
    sp_config = GPTConfig.tiny(attention_impl="ring", sp_mesh=mesh, dropout=0.0, dtype=jnp.float32)
    prompt = jnp.asarray(np.random.default_rng(1).integers(0, sp_config.vocab_size, (2, 8)))
    out = generate(GPTLMHeadModel(sp_config), variables, prompt, max_new_tokens=4)
    assert out.shape == (2, 12)


def test_gpt_remat_grads_match_no_remat():
    """GPTConfig.remat recomputes activations in the backward; gradients (and the
    packed path) must match the non-remat config exactly."""
    import numpy as np

    from unionml_tpu.models.gpt import GPTConfig, GPTLMHeadModel, init_params, lm_loss
    from unionml_tpu.ops.packing import pack_sequences

    base = dict(dropout=0.0, dtype=jnp.float32, attention_impl="xla")
    plain_cfg = GPTConfig.tiny(**base)
    remat_cfg = GPTConfig.tiny(remat=True, **base)
    variables = init_params(plain_cfg, seq_len=16)
    rng = np.random.default_rng(3)
    packed = pack_sequences(
        [rng.integers(1, plain_cfg.vocab_size, size=int(n)) for n in (9, 6, 12)], 16
    )
    ids = jnp.asarray(packed["input_ids"])
    segs = jnp.asarray(packed["segment_ids"])

    def grads(cfg):
        def loss(params):
            logits = GPTLMHeadModel(cfg).apply({"params": params}, ids, segment_ids=segs)
            return lm_loss(logits, ids, segment_ids=segs)

        return jax.grad(loss)(variables["params"])

    g_plain, g_remat = grads(plain_cfg), grads(remat_cfg)
    for a, b in zip(jax.tree_util.tree_leaves(g_plain), jax.tree_util.tree_leaves(g_remat)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)

    # decode path is untouched by remat: cached generation still works
    from unionml_tpu.models.gpt import generate

    out = generate(GPTLMHeadModel(remat_cfg), variables, jnp.ones((1, 4), jnp.int32), 3, max_len=16)
    assert out.shape == (1, 7)


def test_paged_decode_through_joined_leaf_is_bitwise_dense(tiny):
    """The paged pool's one ``"kv"`` leaf a layer (key beside value in a row),
    written by row scatters through its ``(blocks, heads * block_size, row)``
    view, decodes bitwise like the dense ``{"k","v"}`` cache on the XLA arm:
    same logits every decode step and every chunk, and the rows a table names
    hold exactly the dense cache's columns. Ragged rows, prompts that end
    inside a block, a block order that is not the pool's."""
    from unionml_tpu.models.gpt import KVCacheLayout, block_table_width, init_block_pool, init_cache

    cfg, model, variables = tiny
    bs, steps = 4, 6
    lengths = np.asarray([5, 9, 12])
    batch, width = len(lengths), block_table_width(32, bs)
    rng = np.random.default_rng(3)
    prompts = rng.integers(1, cfg.vocab_size, size=(batch, int(lengths.max()))).astype(np.int32)

    # dense: each row prefilled alone (a batch-1 cache each), then stacked; as
    # long as a table row with its scratch column, so both arms sum as many keys
    max_len = width * bs
    dense = init_cache(cfg, batch, max_len)
    firsts = []
    for r, n in enumerate(lengths):
        logits, row = model.apply(
            variables, jnp.asarray(prompts[r : r + 1, :n]), cache=init_cache(cfg, 1, max_len), position=0
        )
        dense = jax.tree_util.tree_map(lambda full, one: full.at[r].set(one[0]), dense, row)
        firsts.append(logits[0, -1])

    # paged: the dense rows written block-wise into a permuted set of pool blocks
    layout = KVCacheLayout(cfg)
    blocks = batch * (width - 1) + 1
    pool = init_block_pool(cfg, blocks, bs)
    assert set(pool["layer_0"]) == {"kv"}
    assert pool["layer_0"]["kv"].shape == (blocks, cfg.num_heads, bs, 2 * cfg.head_dim)
    assert layout.kernel_key == (cfg.num_heads, 2 * cfg.head_dim)
    assert layout.pool_bytes(pool) == (layout.block_bytes(bs) * blocks,) * 2
    table = rng.permutation(blocks - 1).reshape(batch, width - 1).astype(np.int32)
    table = jnp.asarray(np.concatenate([table, np.full((batch, 1), blocks - 1, np.int32)], axis=1))

    def put(pool_leaf, rows):  # (batch, heads, max_len, row) -> its blocks
        as_blocks = rows.reshape(batch, cfg.num_heads, width, bs, -1).transpose(0, 2, 1, 3, 4)
        return pool_leaf.at[table[:, : width - 1]].set(as_blocks[:, : width - 1])

    pool = jax.tree_util.tree_map(put, pool, layout.join(dense))

    lens = jnp.asarray(lengths, jnp.int32)
    tokens = jnp.argmax(jnp.stack(firsts), axis=-1).astype(jnp.int32)
    for _ in range(steps):
        d_logits, dense = model.apply(variables, tokens[:, None], cache=dense, position=lens)
        p_logits, new = model.apply(
            variables, tokens[:, None], cache={"table": table, **pool}, position=lens
        )
        pool = {name: leaf for name, leaf in new.items() if name != "table"}
        np.testing.assert_array_equal(np.asarray(p_logits), np.asarray(d_logits))
        tokens = jnp.argmax(d_logits[:, -1], axis=-1).astype(jnp.int32)
        lens = lens + 1

    # a batch-1 chunk through a table row (the prefix-hit suffix, a chunked
    # prefill's tick) against the same chunk into the dense row
    chunk = jnp.asarray(rng.integers(1, cfg.vocab_size, size=(1, 3)).astype(np.int32))
    for r in range(batch):
        one = jax.tree_util.tree_map(lambda leaf: leaf[r : r + 1], dense)
        d_logits, one = model.apply(variables, chunk, cache=one, position=lens[r])
        dense = jax.tree_util.tree_map(lambda full, row: full.at[r].set(row[0]), dense, one)
        p_logits, new = model.apply(
            variables, chunk, cache={"table": table[r : r + 1], **pool}, position=lens[r]
        )
        pool = {name: leaf for name, leaf in new.items() if name != "table"}
        np.testing.assert_array_equal(np.asarray(p_logits), np.asarray(d_logits))
    lens = lens + chunk.shape[1]

    for name, layer in pool.items():
        rows = layer["kv"][table]  # (batch, width, heads, bs, 2 * head_dim)
        rows = jnp.moveaxis(rows, 2, 1).reshape(batch, cfg.num_heads, width * bs, -1)
        got = layout.split({name: {"kv": rows}})[name]
        for r, n in enumerate(np.asarray(lens)):
            for key in ("k", "v"):
                np.testing.assert_array_equal(
                    np.asarray(got[key][r, :, :n]), np.asarray(dense[name][key][r, :, :n])
                )

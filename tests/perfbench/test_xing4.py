"""The ``xing4`` family at a toy size on the CPU, every mechanism present (1
dense + 2 expert layers, 8 experts top 2 and a shared one, latent attention
with rope and nope parts, 4 residual streams): the program against the plain
reference (``perfbench/reference/xing4.py``), logits and not tokens; a toy
cell of it through ``run.execute`` with its bfloat16 control; its counts.

Tolerances: program and reference are float32 here and compute the same
mathematics in another order (absorbed against expanded attention, sorted
groups against a masked sum over all experts, online against whole softmax),
which leaves 1e-6 to 1e-5 on logits of size 1 to 4; 1e-4 leaves ten times
that and is a hundred times under what a dropped term or a wrong position
would move.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import manifest
from tests.perfbench import tiny

TOL = 1e-4
SIZES = dict(
    hidden_size=64, intermediate_size=96, moe_intermediate_size=32, num_attention_heads=4,
    num_key_value_heads=4, q_lora_rank=24, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, n_routed_experts=8, num_experts_per_tok=2, layers=3,
    first_k_dense_replace=1, vocab_size=512, max_position_embeddings=256,
)


def toy_config():
    config = json.loads((manifest.ROOT / "perfbench/configs/xing4-29b-a4b-serve.json").read_text())
    config.update(SIZES)
    config["rope_scaling"] = dict(config["rope_scaling"], factor=4, original_max_position_embeddings=32)
    config["perfbench"].update(
        name="xing4-toy", compute_dtype="float32", weights_dtype="float32",
        init={},  # the neutral recipe: experts drawn independently, so that a wrong route shows
        kv_bytes=4, act_bytes=4, weights_bytes=4,
        reference_options={"query_block": 8, "vocab_block": 128},
        engine={"num_slots": 2, "max_len": 128, "prefill_buckets": [8, 32], "prefill_chunk": 8,
                "prefill_batch": 1, "prefix_block_size": 4},
    )
    return config


@pytest.fixture(scope="module")
def family():
    return manifest.load_module(manifest.ROOT / "perfbench/families/xing4.py")


@pytest.fixture(scope="module")
def reference():
    return manifest.load_module(manifest.ROOT / "perfbench/reference/xing4.py")


@pytest.fixture(scope="module")
def toy(family):
    config = toy_config()
    return config, family.model(config), family.make_params(config, 2**31 + 11, "float32")


def test_full_forward_is_the_references(toy, family, reference):
    config, model, params = toy
    ids = jnp.asarray(np.random.default_rng(0).integers(0, config["vocab_size"], (1, 40)))
    rows = jnp.arange(40)
    want, chosen, margins = reference.forward(params, ids, rows, **family.reference_kwargs(config))
    logits, sown = model.apply({"params": params}, ids, mutable=["routing"])
    np.testing.assert_allclose(logits[0], want, atol=TOL)
    assert len(chosen) == 2 and chosen[0].shape == (40, 2)  # the two expert layers' choices
    for name, want_chosen, margin in zip(("layer_1", "layer_2"), chosen, margins):
        # the program's own choices, read back: the reference's wherever the choice is no near-tie
        got = np.sort(np.asarray(sown["routing"][name]["moe"]["chosen"][0]), axis=-1)
        same = (got == np.sort(np.asarray(want_chosen), axis=-1)).all(axis=-1)
        assert same[np.asarray(margin) > 1e-5].all() and same.mean() > 0.9
    assert float(jnp.abs(want).max()) > 1.0  # logits of a size that 1e-4 means something for


def test_one_expert_for_every_row_and_one_for_none(toy, family, reference):
    """Routing as uneven as it gets: a selection bias that sends every token's
    two choices to experts 5 and 2, so that 5 and 2 take every row and the
    other six none. The grouped path (empty groups on both sides of the full
    ones) against the reference's masked sum, through the whole model."""
    config, model, params = toy
    skewed = jax.tree.map(lambda x: x, params)
    bias = jnp.zeros((8,), jnp.float32).at[5].set(10.0).at[2].set(5.0)
    for name in ("layer_1", "layer_2"):
        skewed[name]["moe"]["router_bias"] = bias
    ids = jnp.asarray(np.random.default_rng(1).integers(0, config["vocab_size"], (1, 24)))
    want, chosen, _ = reference.forward(skewed, ids, jnp.arange(24), **family.reference_kwargs(config))
    assert all((np.sort(np.asarray(c), axis=-1) == [2, 5]).all() for c in chosen)
    (logits, _), sown = model.apply(
        {"params": skewed}, ids, cache=model.cache_layout().init_cache(1, 24), position=0,
        mutable=["stats", "routing"],
    )
    np.testing.assert_allclose(logits[0], want, atol=TOL)
    for name, want_chosen in zip(("layer_1", "layer_2"), chosen):  # the program's own choices, read back
        got = np.asarray(sown["routing"][name]["moe"]["chosen"][0])
        assert (np.sort(got, axis=-1) == np.sort(np.asarray(want_chosen), axis=-1)).all()
    stats = {k: int(v) for k, v in sown["stats"].items()}
    assert stats == {"expert_rows": 2 * 24 * 2, "experts_hit": 2 * 2, "expert_rows_max": 2 * 24}


@pytest.mark.parametrize("engine", [
    {"prefill_buckets": (32,)}, {"prefill_buckets": (4, 8, 32), "prefill_chunk": 8},
    {"prefill_buckets": (32,), "paged": False},
], ids=["prefill_then_paged_decode", "chunked_prefill_then_paged_decode", "dense_cache"])
def test_prefill_and_decode_through_the_engine_are_the_references_full_forward(toy, family, reference, engine):
    """Logits, not tokens: after admission the engine holds each slot's next
    logits, and after every decode step the next ones; each is the reference's
    full forward over that slot's prompt and answer so far. Two slots of
    different lengths, so rows decode at positions of their own; the chunked
    engine prefills the 19-token prompt in chunks of 8, 8 and (its last 3
    tokens in the 4-token bucket) 4 through the paged prefix (absorbed
    attention over the pool) and the 5-token one in a bucket."""
    from unionml_tpu.serving.continuous import DecodeEngine

    config, model, params = toy
    served = DecodeEngine(model, {"params": params}, num_slots=2, max_len=64, prefix_block_size=4,
                          pipeline=False, **engine)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, config["vocab_size"], n).tolist() for n in (5, 19)]
    slots = served.admit_many([(p, 7) for p in prompts])
    texts = {slot: list(p) for slot, p in zip(slots, prompts)}
    kwargs = family.reference_kwargs(config)
    checked = 0
    while served.busy:
        for slot, text in texts.items():
            if not served._active[slot]:
                continue  # still prefilling its chunks, or done
            ids = np.zeros((1, 32), np.int32)
            ids[0, : len(text)] = text
            want = reference.logits_at(params, jnp.asarray(ids), jnp.asarray([len(text) - 1]), **kwargs)
            np.testing.assert_allclose(served._last_logits[slot], want[0], atol=TOL)
            checked += 1
        for event in served.step():
            if event.emit:
                texts[event.slot].append(event.token)
    assert checked >= 13 and all(len(texts[slot]) == len(p) + 7 for slot, p in zip(slots, prompts))


def test_counts(family):
    """At the cell's sizes, what the issue reckoned: 28.4 M of attention
    weights a layer, 11.0 M an expert, the dense layer 127.5 M, the head 470 M;
    a latent row of 576 values once for all heads."""
    config = json.loads((manifest.ROOT / "perfbench/configs/xing4-29b-a4b-serve.json").read_text())
    parts = family.layer_matmul_params(config)
    assert parts["attention"] == 3584 * 768 + 768 * 6144 + 3584 * 576 + 512 * 8192 + 4096 * 3584 == 28_409_856
    assert parts["expert"] == 3 * 3584 * 1024 and parts["dense_mlp"] + parts["attention"] == 127_500_288
    assert parts["hyper_connections"] == 2 * 4 * 3584 * 24
    per_row = 6 * (parts["attention"] + parts["hyper_connections"]) + parts["dense_mlp"] \
        + 5 * (5 * parts["expert"] + 3584 * 64) + 3584 * 131072
    assert family.matmul_params(config) == per_row
    assert family.decode_flops(config, [1000.0]) == 2.0 * per_row + 6 * 2.0 * 32 * (576 + 512) * 1000
    assert family.decode_attention_bytes(config, [1000.0, 10.0], 2, 2) == 6 * (1010 * 576 * 2 + 2 * 32 * 1088 * 2)
    assert family.decode_expert_bytes(config, 61, 128, 2, 2) == 61 * 3 * 3584 * 1024 * 2 + 128 * 2 * 3584 * 2
    assert family.expert_load_max_over_mean(config, 6, 128) == 3.0
    tree = jax.eval_shape(lambda: family.make_params(config, 1, "bfloat16"))
    assert sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree.leaves(tree)) == 9_596_000_784


def test_the_cut_is_what_reduced_lists():
    """``reduced`` names the keys that differ from ``published``, and no other
    does. The depth run is ``layers`` and what the family builds;
    ``num_hidden_layers`` stays the source's 40 (the driver compares it with
    the source, and ``test_manifest`` takes ``hidden`` in ``reduced`` for a width)."""
    config = json.loads((manifest.ROOT / "perfbench/configs/xing4-29b-a4b-serve.json").read_text())
    assert sorted(config["reduced"]) == sorted(config["published"])
    assert all(config[key] != config["published"][key] for key in config["reduced"])
    assert config["num_hidden_layers"] == config["published"]["layers"] == 40
    built = manifest.Cell(manifest.load(), "xing4-29b-a4b.fewshot-closed").family().program_config(config)
    assert built.num_layers == config["layers"] == 6 and built.first_k_dense_replace == 1


# ------------------------------------------------------- a toy cell of the family


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tiny.make_root(tmp_path_factory.mktemp("bench"))
    (root / "tinybench/configs/xing4-toy.json").write_text(json.dumps(toy_config()))
    mix = json.loads((root / "tinybench/traffic/tiny-closed.json").read_text())
    mix["prompt_tokens"].update(median=12, min=4, max=28)  # some prompts past the 8-token chunk
    (root / "tinybench/traffic/xing4-toy-closed.json").write_text(json.dumps(mix))
    (root / "tinybench/limits/xing4-toy.closed.json").write_text(json.dumps(
        {"sample_requests": 40, "reference_pad_to": 64, "logit_gap": 0.002, "controls": ["bf16"]}))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    real = next(c for c in bench["configs"] if c["name"] == "xing4-29b-a4b-serve")
    bench["configs"].append(dict(real, name="xing4-toy", file="tinybench/configs/xing4-toy.json"))
    bench["workloads"].append({"name": "xing4-toy.closed", "config": "xing4-toy",
                               "traffic": "xing4-toy-closed", "chips": 1, "why": "toy cell for the CPU tests"})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if "xing4-29b-a4b.fewshot-closed" in metric.get("workloads", ()):
            metric["workloads"].append("xing4-toy.closed")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_toy_cell_is_correct_and_its_bfloat16_control_is_not(root):
    """The whole run on the toy cell (bucket and chunked prefill, paged decode,
    the check against the reference): inside the limit, and the reference in
    the precision below the one the cell states, bfloat16, outside it."""
    result = tiny.execute(root, "xing4-toy.closed", control=1, seconds=2.0)
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    compared = {k: v["value"] for k, v in result["compared"].items()}
    limit = result["compared"]["logit_gap"]["limit"]
    # every finished request is compared (some hundreds of tokens): over a handful
    # the bfloat16 reference can put the same token first everywhere and read 0
    assert compared["logit_gap"] <= limit < compared["control_bf16_logit_gap"]
    assert compared["tokens_compared"] >= 100 and compared["short_answers"] == 0


@pytest.mark.parametrize("fault", ["next_expert", "sparse_next_expert", "weights_rolled"])
def test_a_planted_routing_fault_fails_the_toy_cell(root, fault):
    """The comparison has to see routing and not only precision: the same run
    with a fault in the program's grouped dispatch (every pair to the next
    expert; that at every 64th position of a call only; a position's weights on
    the wrong experts of its own set) is not correct. The chip run of
    ``tools/xing4_routing_probe.py`` reads the same three at the cell's size."""
    probe = manifest.load_module(manifest.ROOT / "tools/xing4_routing_probe.py")
    with probe.planted(fault, toy_config()["n_routed_experts"]):
        result = tiny.execute(root, "xing4-toy.closed")
    assert result["correct"] is False and result["failed"] == 0
    assert result["compared"]["logit_gap"]["value"] > 10 * result["compared"]["logit_gap"]["limit"]


def test_the_reference_returns_near_ties_flat_and_a_control_never(toy, family, reference):
    """``tie_margin``: positions where any expert layer's last chosen and first
    left-out scores lie closer than it come back with flat float32 logits (no
    token there lies under the best), the others untouched; a control's logits
    are never flattened, it answers everywhere."""
    config, _, params = toy
    ids = jnp.asarray(np.random.default_rng(3).integers(0, config["vocab_size"], (1, 40)))
    rows, kwargs = jnp.arange(40), family.reference_kwargs(config)
    assert kwargs["tie_margin"] == 0.0  # the toy cell compares every position
    plain, _, margins = reference.forward(params, ids, rows, **kwargs)
    narrowest = np.min(np.stack([np.asarray(m) for m in margins]), axis=0)
    cut = float(np.median(narrowest))
    got = np.asarray(reference.logits_at(params, ids, rows, **dict(kwargs, tie_margin=cut)))
    kept = narrowest >= cut
    assert 0 < kept.sum() < 40
    np.testing.assert_array_equal(got[kept], np.asarray(plain)[kept])
    assert (got[~kept] == 0.0).all()
    low = np.asarray(reference.logits_at(params, ids, rows, lowp="bf16", **dict(kwargs, tie_margin=cut)))
    assert (np.abs(low).max(axis=-1) > 0.1).all()


def test_traced_toy_cell_reads_the_expert_counters(root):
    result = tiny.execute(root, "xing4-toy.closed", trace=1)
    assert result["correct"] is True
    assert {"expert_load_max_over_mean", "slot_occupancy", "compiles_in_window.serve"} <= set(result["metrics"])
    # 2 rows x top 2 over 8 experts: the busiest of a step has 1 to 4 rows against a mean of 1/2
    assert 2.0 <= result["metrics"]["expert_load_max_over_mean"]["value"] <= 8.0
    assert "routed_experts_roofline" not in result["metrics"]  # a device time: silent off the chip


def test_readers_are_silent_on_a_program_without_the_counters(root):
    """As the parent commit is: no ``expert_rows`` in ``/stats``, no kernel names."""
    cell = tiny.cell(root, "xing4-toy.closed")
    stats = {"generation": {"pipeline": {"step_dispatches": 3}}}
    ctx = {"config": cell.config, "family": cell.family(), "load": {"stats_open": stats, "stats_close": stats},
           "trace": None, "peaks": {"hbm_bytes_per_s": 1.0}}
    assert cell.reader("routed_experts_roofline")(ctx) is None
    assert cell.reader("expert_load_max_over_mean")(ctx) is None

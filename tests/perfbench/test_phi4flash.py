"""The ``phi4flash`` family at a toy size on the CPU, every mechanism present
(8 layers: 3 Mamba, 2 window of 8 keys, 1 full, 1 gated memory unit, 1 cross;
``d_state`` 4, blocks of 4 tokens): the program against the plain reference
(``perfbench/reference/phi4flash.py``), logits and not tokens; a toy cell of it
through ``run.execute`` with its bfloat16 control and its planted faults; its
counts against hand arithmetic.

Tolerances: program and reference are float32 here and compute the same
mathematics in another order (zero-padded queries over joined rows against
separate products, online against whole softmax, a ring against masks), which
leaves 1e-5 to 1e-4 on logits of size 3 to 6; 3e-4 leaves room over that and is
a hundred times under what a dropped term or a wrong position moves.
"""

import contextlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import manifest
from tests.perfbench import tiny

TOL = 3e-4
CELL = "phi4-mini-flash.reason-closed"
SIZES = dict(
    hidden_size=32, intermediate_size=48, num_attention_heads=8, num_key_value_heads=4,
    num_hidden_layers=8, sliding_window=8, vocab_size=512, max_position_embeddings=256,
)


def toy_config():
    config = json.loads((manifest.ROOT / "perfbench/configs/phi4-mini-flash-serve.json").read_text())
    config.update(SIZES)
    config["assumed"]["mamba"] = {"d_state": 4, "d_conv": 4, "expand": 2, "dt_rank": 2}
    config["perfbench"].update(
        name="phi4flash-toy", compute_dtype="float32", weights_dtype="float32",
        # hotter than the cell's recipe: eight toy layers have to amplify rounding as 32 real ones do
        init={"gain": 2.0, "residual_gain": 1.0},
        kv_bytes=4, act_bytes=4, weights_bytes=4,
        reference_options={"query_block": 8, "vocab_block": 128},
        engine={"num_slots": 2, "max_len": 64, "prefill_buckets": [4, 8, 32], "prefill_chunk": 6,
                "prefill_batch": 1, "prefix_block_size": 4},
    )
    return config


@pytest.fixture(scope="module")
def family():
    return manifest.load_module(manifest.ROOT / "perfbench/families/phi4flash.py")


@pytest.fixture(scope="module")
def reference():
    return manifest.load_module(manifest.ROOT / "perfbench/reference/phi4flash.py")


@pytest.fixture(scope="module")
def toy(family):
    config = toy_config()
    return config, family.model(config), family.make_params(config, 2**31 + 11, "float32")


def expected(reference, family, config, params, text, pad=64):
    ids = np.zeros((1, pad), np.int32)
    ids[0, : len(text)] = text
    return reference.logits_at(
        params, jnp.asarray(ids), jnp.asarray([len(text) - 1]), **family.reference_kwargs(config)
    )[0]


def test_full_forward_is_the_references(toy, family, reference):
    config, model, params = toy
    ids = jnp.asarray(np.random.default_rng(0).integers(0, config["vocab_size"], (1, 40)))
    want = reference.logits_at(params, ids, jnp.arange(40), **family.reference_kwargs(config))
    logits = model.apply({"params": params}, ids)
    np.testing.assert_allclose(logits[0], want, atol=TOL)
    assert float(jnp.abs(want).max()) > 1.0  # logits of a size that the tolerance means something for
    # with logit_rows the layers after the full one run for that position alone: the same logits there
    one = model.apply({"params": params}, ids, logit_rows=jnp.asarray([23]))
    np.testing.assert_allclose(one[0, 0], want[23], atol=TOL)


def serve(toy, family, reference, lengths, budget, check=True, **engine):
    """Admit prompts of ``lengths`` together and decode ``budget`` tokens each;
    after admission and after every step each active slot's next logits are the
    reference's full forward over its prompt and answer so far."""
    from unionml_tpu.serving.continuous import DecodeEngine

    config, model, params = toy
    options = dict(num_slots=2, max_len=64, prefix_block_size=4, prefill_buckets=(32,), pipeline=False)
    served = DecodeEngine(model, {"params": params}, **{**options, **engine})
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, config["vocab_size"], n).tolist() for n in lengths]
    slots = served.admit_many([(p, budget) for p in prompts])
    texts = {slot: list(p) for slot, p in zip(slots, prompts)}
    worst = checked = 0
    while served.busy:
        for slot, text in texts.items():
            if served._active[slot]:
                want = expected(reference, family, config, params, text)
                worst = max(worst, float(jnp.abs(served._last_logits[slot] - want).max()))
                checked += 1
        for event in served.step():
            if event.emit:
                texts[event.slot].append(event.token)
    assert all(len(texts[slot]) == len(p) + budget for slot, p in zip(slots, prompts))
    if check:
        assert worst <= TOL and checked >= budget
    return served, [texts[slot] for slot in slots]


@pytest.mark.parametrize("engine", [
    {}, {"prefill_buckets": (4, 8, 32), "prefill_chunk": 6},
], ids=["bucket_prefill", "chunked_prefill"])
def test_prefill_then_paged_decode_past_two_windows_is_the_references_full_forward(toy, family, reference, engine):
    """Two slots of different lengths in one step (5 and 19 tokens), 22 tokens
    decoded each: past two windows of 8 and round the ring of 3 blocks of 4 more
    than once. The chunked engine prefills the 19 tokens in chunks of 6, 6, 6
    and 1: the chunk edges fall inside a window and inside the convolution's 4
    taps, and every chunk starts from the state and the ring the one before
    left."""
    served, _ = serve(toy, family, reference, (5, 19), 22, **engine)
    stats = served.pipeline_stats()
    assert stats["state_resets"] == 2
    # positions that did not enter the gated-memory and cross layers: all but the one read, a call
    assert stats["cross_rows_skipped"] == (4 + 18 if "prefill_chunk" not in engine else 4 + 5 + 5 + 5 + 0)


def test_the_pipelined_engine_emits_the_unpipelined_engines_tokens(toy, family, reference):
    """Depth-1 pipelining dispatches a step before the last one's tokens are
    fetched, so the host's text lags the device's logits and they cannot be
    compared step by step: the streams are, with the unpipelined engine's, which
    the test above holds to the reference."""
    _, plain = serve(toy, family, reference, (5, 19), 22, check=False)
    _, piped = serve(toy, family, reference, (5, 19), 22, check=False, pipeline=True,
                     prefill_buckets=(4, 8, 32), prefill_chunk=6)
    assert piped == plain


@pytest.mark.parametrize("length", [7, 8, 9])
def test_prompts_of_a_window_less_one_a_window_and_a_window_more_one(toy, family, reference, length):
    serve(toy, family, reference, (length,), 12)


def test_a_reused_slot_gives_the_second_request_its_logits_as_if_alone(toy, family, reference):
    """One slot, two requests in turn: the second's state, ring and full cache
    are its own from its first logits on."""
    from unionml_tpu.serving.continuous import DecodeEngine

    config, model, params = toy
    served = DecodeEngine(model, {"params": params}, num_slots=1, max_len=64, prefix_block_size=4,
                          prefill_buckets=(4, 8, 32), prefill_chunk=6)
    rng = np.random.default_rng(5)
    for length in (21, 5, 13):  # chunked, a bucket, chunked again
        prompt = rng.integers(0, config["vocab_size"], length).tolist()
        text = prompt + served.generate(prompt, 6)
        for cut in (length, length + 3):  # the token after the prompt, and one decoded later
            want = expected(reference, family, config, params, text[:cut])
            assert float(want.max() - want[text[cut]]) <= TOL  # the reference's best, to a near-tie
    assert served.pipeline_stats()["state_resets"] == 3


def test_the_window_layers_resident_bytes_do_not_grow_with_length(family):
    """At the cell's sizes: a slot's ring and state are what they are at any
    length, and only the full layer's blocks grow."""
    config = json.loads((manifest.ROOT / "perfbench/configs/phi4-mini-flash-serve.json").read_text())
    layout = family.model(config).cache_layout()
    fixed = layout.slot_bytes(128)
    assert fixed == {"state": 9 * (5120 * 16 * 4 + 3 * 5120 * 2), "ring": 8 * 5 * 128 * 5120}
    assert layout.block_bytes(128) == 128 * 5120  # one layer's keys and values, not 32 layers'
    pool = jax.eval_shape(lambda: layout.init_block_pool(64 * 32 + 1, 128, num_slots=64))
    assert pool["layer_1"]["kv"].shape == (64 * 5 + 1, 10, 128, 256)  # whatever max_len is
    assert pool["layer_17"]["kv"].shape == (64 * 32 + 1, 10, 128, 256)
    assert pool["layer_0"]["ssm"].shape == (64, 16, 5120) and "layer_18" not in pool and "layer_19" not in pool
    grown = [family.resident_bytes(config, n, 2) for n in (1024, 4096)]
    assert grown[1] - grown[0] == 3072 * 5120  # the one full layer's rows and nothing of the window layers'


def test_counts(family):
    """At the cell's sizes, what the issue reckoned: Mamba layer 119.8 M, window
    or full layer 98.3 M, gated-memory layer 104.9 M, cross layer 91.7 M,
    embedding 512.2 M, 3.85 B in all; the full cache read eight times, the ring
    eight layers x min(length, 512); the state in and out."""
    config = json.loads((manifest.ROOT / "perfbench/configs/phi4-mini-flash-serve.json").read_text())
    assert config["reduced"] == [] and family.layer_counts(config) == {
        "mamba": 9, "window": 8, "full": 1, "gmu": 7, "cross": 7}
    parts = family.layer_matmul_params(config)
    mlp = 3 * 2560 * 10240
    assert parts["mamba"] == 2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560 and parts["mlp"] == mlp
    assert parts["window"] == parts["full"] == 2560 * 5120 + 2560 * 2560
    assert parts["gmu"] == 2 * 2560 * 5120 and parts["cross"] == 2 * 2560 * 2560
    per_row = 9 * parts["mamba"] + 9 * parts["full"] + 7 * parts["gmu"] + 7 * parts["cross"] + 32 * mlp \
        + 200064 * 2560
    assert family.matmul_params(config) == per_row
    tree = jax.eval_shape(lambda: family.make_params(config, 1, "bfloat16"))
    count = lambda t: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(t))
    assert [round(count(tree[f"layer_{i}"]) / 1e6, 1) for i in (0, 1, 17, 18, 19)] == [119.9, 98.3, 98.3, 104.9, 91.8]
    assert round(count(tree["embed"]) / 1e6, 1) == 512.2 and round(count(tree) / 1e9, 2) == 3.85
    assert round(sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree)) / 1e9, 2) == 7.71
    attention = 2.0 * 40 * 192 * (8 * 1000 + 8 * 512)
    assert family.decode_flops(config, [1000.0]) == 2.0 * per_row + 9 * 7.0 * 5120 * 16 + attention
    row, query = 5120, 40 * 192 * 2
    assert family.decode_window_bytes(config, [1000.0, 100.0], 2, 2) == 8 * (612 * row + 2 * query)
    assert family.decode_attention_bytes(config, [1000.0, 100.0], 2, 2) == 8 * (1100 * row + 2 * query) \
        + 8 * (612 * row + 2 * query)
    assert family.decode_state_bytes(config, 64) == 9 * 64 * (2 * 5120 * 16 * 4 + (3 * 5120 + 32) * 4)
    assert family.prefill_scan_bytes(config, [1000.0, 24.0], 2) == 9 * (1024 * (5120 * 10 + 64) + 2 * 2 * 5120 * 16 * 4)
    assert family.resident_bytes(config, 870, 2) == 9 * (5120 * 16 * 4 + 3 * 5120 * 2) + 8 * 512 * row + 870 * row
    assert family.resident_bytes(config, 870, 2) / 870 < 36_000 < 32 * 5120 == 163_840  # a token, against 32 per-head layers'


def test_the_state_decides_tokens(toy):
    """The recipe's check: with the recurrent state zeroed before every decode
    step, most of the greedy tokens change (weights under which the state were
    idle could not tell a state that is carried from one that is not)."""
    from unionml_tpu.serving.continuous import DecodeEngine

    config, model, params = toy
    prompt = np.random.default_rng(7).integers(0, config["vocab_size"], 20).tolist()

    def answer(zeroed):
        served = DecodeEngine(model, {"params": params}, num_slots=1, max_len=64, prefix_block_size=4,
                              prefill_buckets=(32,), pipeline=False)
        served.admit_many([(prompt, 24)])
        tokens = []
        while served.busy:
            if zeroed:
                served._pool = {
                    name: {k: jnp.zeros_like(v) if k == "ssm" else v for k, v in layer.items()}
                    for name, layer in served._pool.items()
                }
            tokens += [e.token for e in served.step() if e.emit]
        return tokens

    kept, zeroed = answer(False), answer(True)
    assert len(kept) == len(zeroed) == 24
    assert sum(a != b for a, b in zip(kept, zeroed)) > 12


# ------------------------------------------------------- a toy cell of the family


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tiny.make_root(tmp_path_factory.mktemp("bench"))
    (root / "tinybench/configs/phi4flash-toy.json").write_text(json.dumps(toy_config()))
    mix = json.loads((root / "tinybench/traffic/tiny-closed.json").read_text())
    mix["prompt_tokens"].update(median=10, min=3, max=30)  # prompts under a window, over it, and past the 6-token chunk
    mix["output_tokens"].update(median=10, min=4, max=20)
    (root / "tinybench/traffic/phi4flash-toy-closed.json").write_text(json.dumps(mix))
    (root / "tinybench/limits/phi4flash-toy.closed.json").write_text(json.dumps(
        {"sample_requests": 40, "reference_pad_to": 64, "logit_gap": 0.002, "controls": ["bf16"]}))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    real = next(c for c in bench["configs"] if c["name"] == "phi4-mini-flash-serve")
    bench["configs"].append(dict(real, name="phi4flash-toy", file="tinybench/configs/phi4flash-toy.json"))
    bench["workloads"].append({"name": "phi4flash-toy.closed", "config": "phi4flash-toy",
                               "traffic": "phi4flash-toy-closed", "chips": 1, "why": "toy cell for the CPU tests"})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if CELL in metric.get("workloads", ()):
            metric["workloads"].append("phi4flash-toy.closed")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_toy_cell_is_correct_and_its_bfloat16_control_is_not(root):
    """The whole run on the toy cell (bucket and chunked prefill, paged decode
    on a ring, the check against the reference): inside the limit, and the
    reference in the precision below the one the cell states outside it."""
    result = tiny.execute(root, "phi4flash-toy.closed", control=1, seconds=2.0)
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    compared = {k: v["value"] for k, v in result["compared"].items()}
    limit = result["compared"]["logit_gap"]["limit"]
    assert compared["logit_gap"] <= limit < compared["control_bf16_logit_gap"]
    assert compared["tokens_compared"] >= 100 and compared["short_answers"] == 0


@contextlib.contextmanager
def planted(fault):
    """The program with one fault in it, for as long as the block lasts."""
    from unionml_tpu.models import phi4flash
    from unionml_tpu.ops import ssm

    patch = pytest.MonkeyPatch()
    if fault == "state_not_carried_over_a_chunk_edge":
        real = phi4flash.Mamba._carried

        def carried(self, cache, call, batch):
            state, tail = real(self, cache, call, batch)
            return (jnp.zeros_like(state), tail) if call.mode == "chunk" else (state, tail)

        patch.setattr(phi4flash.Mamba, "_carried", carried)
    elif fault == "state_not_zeroed_at_reuse":
        real = phi4flash.HybridCacheLayout.insert_slot_state

        def keep_old_state(self, pool, local_cache, slots, lengths):
            out = real(self, pool, local_cache, slots, lengths)
            return {name: ({**layer, "ssm": pool[name]["ssm"]} if "ssm" in layer else layer)
                    for name, layer in out.items()}

        patch.setattr(phi4flash.HybridCacheLayout, "insert_slot_state", keep_old_state)
    elif fault in ("window_of_7", "window_of_9"):
        real = phi4flash.Attention.__call__
        wrong = 7 if fault.endswith("7") else 9

        def call(self, x, cache, how):
            if self.window is not None:
                object.__setattr__(self, "window", wrong)
            return real(self, x, cache, how)

        patch.setattr(phi4flash.Attention, "__call__", call)
    elif fault == "cross_layer_reads_its_own_keys":
        real = phi4flash.CrossAttention.__call__

        def own(self, x, shared, how):
            return real(self, x, jnp.roll(shared, 1, axis=-1), how)

        patch.setattr(phi4flash.CrossAttention, "__call__", own)
    elif fault == "lambda_init_of_another_layer":
        real = phi4flash.lambda_init
        patch.setattr(phi4flash, "lambda_init", lambda layer: real(layer + 2))
    elif fault == "bucket_padding_enters_the_state":
        real = ssm.selective_scan
        patch.setattr(phi4flash, "selective_scan", lambda *a, **kw: real(*a[:-1], None, **kw))
    else:
        raise ValueError(fault)
    try:
        yield
    finally:
        patch.undo()


@pytest.mark.parametrize("fault", [
    "state_not_carried_over_a_chunk_edge", "state_not_zeroed_at_reuse", "window_of_7", "window_of_9",
    "cross_layer_reads_its_own_keys", "lambda_init_of_another_layer", "bucket_padding_enters_the_state",
])
def test_a_planted_fault_fails_the_toy_cell(root, fault):
    """The comparison has to see the mechanisms and not only precision: the
    same run with one fault in the program is not correct."""
    with planted(fault):
        result = tiny.execute(root, "phi4flash-toy.closed", seconds=2.0)
    assert result["correct"] is False and result["failed"] == 0
    assert result["compared"]["logit_gap"]["value"] > 5 * result["compared"]["logit_gap"]["limit"]


def test_traced_toy_cell_reads_the_residency_counters(root):
    result = tiny.execute(root, "phi4flash-toy.closed", trace=1)
    assert result["correct"] is True
    assert {"cache_resident_bytes_per_token", "slot_occupancy", "compiles_in_window.serve"} <= set(result["metrics"])
    # 2 slots of 10 to 50 live tokens: 3 Mamba states, 2 rings of 3 blocks and a few full-layer blocks each
    layout = tiny.cell(root, "phi4flash-toy.closed").family().model(toy_config()).cache_layout()
    fixed = sum(layout.slot_bytes(4).values())
    value = result["metrics"]["cache_resident_bytes_per_token"]["value"]
    assert fixed / 50 < value < fixed / 3 + 2 * layout.block_bytes(4)
    # device times: silent off the chip
    assert not {"ssm_scan_roofline", "window_attn_roofline", "paged_attn_roofline"} & set(
        result["metrics"])


def test_readers_are_silent_on_a_program_without_the_counters(root):
    """As the parent commit is: no residency counters in ``/stats``; and on a
    configuration without the kernels' names."""
    cell = tiny.cell(root, "phi4flash-toy.closed")
    stats = {"generation": {"pipeline": {"step_dispatches": 3}}}
    ctx = {"config": cell.config, "family": cell.family(), "load": {"stats_open": stats, "stats_close": stats},
           "trace": None, "peaks": {"hbm_bytes_per_s": 1.0}}
    for name in ("cache_resident_bytes_per_token", "ssm_scan_roofline", "window_attn_roofline"):
        assert cell.reader(name)(ctx) is None
    other = tiny.cell(root, "tiny.closed")
    ctx = dict(ctx, config=other.config, family=other.family(), trace=object())
    for name in ("ssm_scan_roofline", "window_attn_roofline"):
        assert cell.reader(name)(ctx) is None


def test_the_configuration_keeps_the_catalogs_keys(family):
    """``reduced`` is empty: the catalog's ``config`` is in the file key for
    key, and what it does not give is under ``assumed`` with its source."""
    config = json.loads((manifest.ROOT / "perfbench/configs/phi4-mini-flash-serve.json").read_text())
    catalog = {"embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560, "intermediate_size": 10240,
               "layer_norm_eps": 1e-05, "max_position_embeddings": 262144, "mb_per_layer": 2,
               "model_type": "phi4flash", "num_attention_heads": 40, "num_hidden_layers": 32,
               "num_key_value_heads": 20, "resid_pdrop": 0, "sliding_window": 512, "tie_word_embeddings": True,
               "mlp_bias": False, "lm_head_bias": False, "vocab_size": 200064}
    assert {key: config[key] for key in catalog} == catalog and config["reduced"] == []
    assert config["assumed"]["mamba"] == {"d_state": 16, "d_conv": 4, "expand": 2, "dt_rank": 160}
    assert all(name in " ".join(map(str, config["assumed"].values()))
               for name in ("arXiv:2312.00752", "arXiv:2507.06607", "arXiv:2410.05258"))
    built = family.program_config(config)
    assert (built.d_inner, built.rank, built.head_dim, built.groups, built.full_layer) == (5120, 160, 64, 10, 17)
    engine = config["perfbench"]["engine"]
    assert engine["num_slots"] == 64 and engine["max_len"] == 4096 and "prefix_cache_blocks" not in engine


def test_the_scan_reader_finds_its_kernel_by_the_start_of_the_name():
    """A recorded shape of the chip's trace (my chip run, PR 35): the Mosaic
    call returns a tuple, so ``short_op_name`` leaves its instruction whole,
    ``%`` and all; what takes its result as an operand holds the name too and
    is not the kernel; an operation outside the prefill programs is not
    counted."""
    from perfbench import opnames, trace

    kernel = ("%ssm_scan.9 = (f32[1,256,1,5120]{3,2,1,0:T(1,128)S(1)}, f32[1,16,5120]{2,1,0:T(8,128)}) "
              'custom-call(%broadcast.60, %fusion.1248), custom_call_target="tpu_custom_call"')
    user = "%fusion.501 = bf16[1,256,5120]{2,1,0} fusion(%ssm_scan.9), kind=kLoop"
    device = trace.DeviceTrace("/device:TPU:0", modules=[("jit__prefill_wave(1)", 0.0, 1.0), ("jit__multi(2)", 1.0, 2.0)],
                               ops=[(kernel, 0.1, 0.3), (user, 0.3, 0.35), (kernel, 1.2, 1.5)])
    summary = trace.TraceSummary([device], [], (0.0, 2.0))
    runs = summary.module_runs(["_prefill"])
    assert opnames.seconds_within(summary, ["ssm_scan"], runs) == pytest.approx(0.2)
    assert opnames.seconds_within(summary, ["ssm_step"], runs) == 0.0
    cell = manifest.Cell(manifest.load(), CELL)
    load = {"records": [{"prompt_len": 200, "token_times": [0.5, 0.6]}, {"prompt_len": 900, "token_times": [5.0]},
                        {"prompt_len": 50, "token_times": []}]}
    ctx = {"config": cell.config, "family": cell.family(), "trace": summary, "trace_interval": (0.0, 2.0),
           "load": load, "peaks": {"hbm_bytes_per_s": 819e9}}
    share = cell.reader("ssm_scan_roofline")(ctx)
    moved = 9 * (200 * (5120 * 10 + 64) + 2 * 5120 * 16 * 4)  # the one prompt whose first token came in the interval
    assert share == pytest.approx(100.0 * moved / 819e9 / 0.2)

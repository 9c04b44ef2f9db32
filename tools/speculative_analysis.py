"""Speculative decoding: measured acceptance + the device-local speedup math.

If speculative decoding cannot be shown beating plain decode on the hardware
at hand, document where it WOULD pay, with the math. The two
inputs to that math are measurable without TPU hardware:

- the ACCEPTANCE RATE ``alpha`` is a property of the (target, draft) model pair
  — measured here by training a 4-layer char-GPT target and a 1-layer draft on
  the same corpus (CPU, minutes) and running the real rejection-sampling loop
  (``models/speculative.py``); reported separately for in-distribution prompts
  (substrings of the training text) and a HELD-OUT sentence excluded from
  training;
- the COST RATIO ``rho = c_draft / c_target`` (per-token step costs) is set by
  the architectures; measured here on CPU and computable for any pair from
  layer counts (decode steps are memory/layer-bound: rho ~ L_draft / L_target).

The standard result (Leviathan et al. 2023): with draft length ``gamma``, one
verify cycle costs ``gamma * c_d + c_t`` and emits on average

    E[tokens] = (1 - alpha^(gamma+1)) / (1 - alpha)

so device-local speedup over plain decode is E[tokens] / (gamma * rho + 1).
The tool evaluates that for the measured alpha at several gammas and for the
rho regimes that matter (2-layer draft of a 12-layer target etc.), and writes
SPECULATIVE_ANALYSIS.json.

Two measurement paths share the trained pair:

- the STATIC-gamma facade loop (``models/speculative.py``) sweeps fixed
  gammas — it isolates how acceptance degrades with draft length;
- the PRODUCTION engine path (``serving/speculative.py``) serves the same
  splits through :class:`SpeculativeEngine` — paged int8 pool, shared block
  tables, per-request adaptive gamma — and reports the acceptance and
  accepted-tokens-per-target-step the adaptive policy actually achieves
  (on hostile traffic gamma decays toward 0, so the engine number is a
  floor at ~1.0 rather than the static loop's collapse).
"""

import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")


def expected_tokens(alpha: float, gamma: int) -> float:
    if alpha >= 1.0:
        return float(gamma + 1)
    return (1.0 - alpha ** (gamma + 1)) / (1.0 - alpha)


def speedup(alpha: float, gamma: int, rho: float) -> float:
    return expected_tokens(alpha, gamma) / (gamma * rho + 1.0)


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from unionml_tpu.models import GPTConfig, GPTLMHeadModel, create_train_state
    from unionml_tpu.models.speculative import speculative_generate
    from unionml_tpu.models.training import fit_lm

    # one corpus, two models: the draft is a truncated-depth sibling — the
    # standard deployment shape (same tokenizer/family, fewer layers)
    # the 4th pangram is HELD OUT of training entirely (alpha on it is the
    # out-of-sample number; alpha on the first three is the memorized bound)
    text = (
        "the quick brown fox jumps over the lazy dog. "
        "pack my box with five dozen liquor jugs. "
        "how vexingly quick daft zebras jump. "
    ) * 80
    heldout_sentence = "sphinx of black quartz, judge my vow. "
    vocab = 128
    corpus = np.frombuffer(text.encode(), dtype=np.uint8).astype(np.int32) % vocab
    rng = np.random.default_rng(0)
    seqs = [
        corpus[i : i + int(n)]
        for i, n in zip(
            rng.integers(0, len(corpus) - 64, size=400), rng.integers(16, 64, size=400)
        )
    ]

    def train(num_layers: int, steps: int):
        cfg = GPTConfig.tiny(
            vocab_size=vocab, hidden_size=64, num_layers=num_layers, num_heads=4,
            max_position_embeddings=128, dropout=0.0, dtype=jnp.float32,
            attention_impl="xla",
        )
        model = GPTLMHeadModel(cfg)
        variables = model.init(
            {"params": jax.random.PRNGKey(num_layers)}, jnp.zeros((1, 64), jnp.int32),
            deterministic=True,
        )
        state = create_train_state(model, variables, learning_rate=3e-3)
        result = fit_lm(
            state, seqs, seq_len=64, batch_size=32, num_steps=steps, pack=True,
            log_every=10_000,
        )
        return model, {"params": result.state.params}

    t0 = time.time()
    target, t_vars = train(num_layers=4, steps=120)
    draft, d_vars = train(num_layers=1, steps=120)
    train_s = time.time() - t0

    prompt_sets = {
        "in_distribution": ["the quick brown ", "pack my box ", "how vexingly "],
        "held_out": [heldout_sentence[:16], heldout_sentence[7:23]],
    }
    measured = []
    for gamma in (2, 4, 8):
        for temperature in (0.0, 0.8):
            for split, prompts in prompt_sets.items():
                accepted = proposed = 0
                for i, prompt in enumerate(prompts):
                    ids = jnp.asarray([[c % vocab for c in prompt.encode()]], jnp.int32)
                    _, stats = speculative_generate(
                        target, t_vars, draft, d_vars, ids, max_new_tokens=48,
                        gamma=gamma, temperature=temperature,
                        rng=jax.random.PRNGKey(i), return_stats=True,
                    )
                    accepted += int(stats["accepted"])
                    proposed += int(stats["proposed"])
                alpha = accepted / proposed if proposed else 0.0
                measured.append({
                    "gamma": gamma, "temperature": temperature, "split": split,
                    "alpha": round(alpha, 4),
                })
                print(f"[spec] gamma={gamma} T={temperature} {split}: alpha={alpha:.3f}",
                      file=sys.stderr)

    # the production adaptive-gamma path: the same splits served through the
    # paged int8 SpeculativeEngine. Counter deltas around each split give the
    # split-attributed acceptance and accepted-tokens-per-target-step
    # (fallback rounds count as target steps — degradation stays visible).
    from unionml_tpu.serving.speculative import SpeculativeEngine

    engine_measured = []
    for temperature in (0.0, 0.8):
        engine = SpeculativeEngine(
            target, t_vars, draft, d_vars, num_slots=4, max_len=128,
            prefill_buckets=(16,), prefix_block_size=4, prefix_cache_blocks=64,
            kv_quantize="int8", seed=11, temperature=0.0,
        )
        for split, prompts in prompt_sets.items():
            before = (engine.spec_accepted, engine.spec_proposed,
                      engine.spec_slot_rounds, engine.spec_fallback_rounds)
            for i, prompt in enumerate(prompts):
                ids = np.asarray([c % vocab for c in prompt.encode()], np.int32)
                sampling = {"speculative": True}
                if temperature > 0:
                    sampling.update(temperature=temperature, seed=1000 + i)
                engine.admit_many([(ids, 48, sampling)])
                while (engine.num_active or engine.has_pending_prefill
                       or engine.has_pending_events):
                    engine.step(1)
            accepted = engine.spec_accepted - before[0]
            proposed = engine.spec_proposed - before[1]
            ran = (engine.spec_slot_rounds - before[2]) + (
                engine.spec_fallback_rounds - before[3]
            )
            engine_measured.append({
                "temperature": temperature, "split": split,
                "alpha": round(accepted / proposed, 4) if proposed else 0.0,
                "accepted_per_target_step": (
                    round((accepted + ran) / ran, 4) if ran else None
                ),
                "fallback_rounds": engine.spec_fallback_rounds - before[3],
            })
            print(f"[spec-engine] T={temperature} {split}: "
                  f"alpha={engine_measured[-1]['alpha']:.3f} "
                  f"apts={engine_measured[-1]['accepted_per_target_step']}",
                  file=sys.stderr)

    # device-local speedup projections: rho from layer ratios (decode is
    # per-layer bound), spanning the measured pair (1/4) and deployment shapes.
    # Each gamma row uses ITS OWN measured greedy held-out alpha — acceptance
    # degrades with gamma, and mixing one gamma's alpha into another's cycle
    # formula would inflate the numbers.
    alpha_by_gamma = {
        m["gamma"]: m["alpha"]
        for m in measured
        if m["temperature"] == 0.0 and m["split"] == "held_out"
    }
    projections = []
    for rho, pair in ((0.25, "1-layer draft / 4-layer target (measured pair)"),
                      (1 / 6, "2-layer draft / 12-layer target (GPT-2 small)"),
                      (1 / 24, "2-layer draft / 48-layer target (large decoder)")):
        for gamma, alpha in sorted(alpha_by_gamma.items()):
            projections.append({
                "rho": round(rho, 4),
                "pair": pair,
                "gamma": gamma,
                "alpha": alpha,
                "alpha_provenance": "greedy, held-out prompts, this gamma",
                "expected_tokens_per_cycle": round(expected_tokens(alpha, gamma), 3),
                "device_local_speedup": round(speedup(alpha, gamma, rho), 3),
            })

    payload = {
        "analysis": "speculative_decoding_value",
        "recorded_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "backend": jax.default_backend(),
        "setup": {
            "target_layers": 4, "draft_layers": 1, "hidden": 64,
            "corpus": "char-level, 3 pangrams; 4th pangram fully held out",
            "train_steps": 120,
            "train_wall_s": round(train_s, 1),
        },
        "measured_acceptance": measured,
        "engine_measured": {
            "provenance": "SpeculativeEngine, paged int8 pool, adaptive gamma "
                          "(init 2, max 4), fallback rounds counted as target "
                          "steps",
            "splits": engine_measured,
        },
        "speedup_model": "E[tokens]=(1-a^(g+1))/(1-a); speedup=E[tokens]/(g*rho+1)",
        "projections": projections,
    }
    out = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                       "SPECULATIVE_ANALYSIS.json")
    with open(out, "w") as fh:
        json.dump(payload, fh, indent=2)
    print(json.dumps({"metric": "speculative_acceptance",
                      "value": alpha_by_gamma.get(4, 0.0), "unit": "accept_rate",
                      "provenance": "greedy, held-out, gamma=4",
                      "projections": len(projections)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

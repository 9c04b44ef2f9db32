"""Flash-attention kernel sweep: pallas (Mosaic) vs XLA at BERT shapes.

The pallas kernels must compile on real hardware
(``interpret=False``), be timed against ``xla_attention``, and have their block sizes
chosen from data. This harness does exactly that:

- sweeps ``(block_q, block_k)`` over MXU-aligned candidates for each shape class
  (seq 128 and 512, head_dim 64 — the BERT-base fine-tune shapes);
- times forward AND forward+backward, steady-state, cold compile excluded;
- records per-shape winners + the pallas-vs-XLA verdict into ``KERNEL_BENCH.json``.
  If the kernel loses to XLA's fused attention at a shape, the recorded verdict is
  ``"use_xla"`` — paste winners into ``unionml_tpu/ops/tuning.py::TUNED_BLOCKS`` only
  where pallas wins.

On CPU there is nothing honest to time (interpret mode is an emulation), so the
harness runs a correctness sweep instead: every candidate block config is validated
numerically (forward and grads) in interpret mode, and the JSON says so.
"""

import json
import sys
import time
from datetime import datetime, timezone


def _time(fn, *args, iters=20, warmup=3, reps=3):
    # best-of-reps: the min is the standard robust timing estimator
    import jax

    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / iters * 1e3)  # ms/iter
    return best


def sweep_tpu(shapes, candidates):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from unionml_tpu.ops.attention import flash_attention, xla_attention

    results = {}
    for batch, heads, seq, head_dim in shapes:
        rng = np.random.default_rng(0)
        q, k, v = (
            jnp.asarray(rng.normal(size=(batch, heads, seq, head_dim)), dtype=jnp.bfloat16)
            for _ in range(3)
        )

        # Amortize INSIDE the device: a lax.scan chains SCAN_N applications
        # (output feeds the next query) in one compiled program, so per-op time
        # is resolved on-chip and per-launch dispatch overhead cannot mask it.
        SCAN_N = 32

        def scanned_fwd(fn):
            @jax.jit
            def run(q, k, v):
                def body(c, _):
                    return fn(c, k, v).astype(c.dtype), None

                out, _ = jax.lax.scan(body, q, None, length=SCAN_N)
                return out

            return run

        def scanned_bwd(fn):
            def loss(q, k, v):
                return jnp.sum(fn(q, k, v).astype(jnp.float32) ** 2)

            grad_fn = jax.grad(loss, argnums=(0, 1, 2))

            @jax.jit
            def run(q, k, v):
                def body(c, _):
                    dq, dk, dv = grad_fn(c, k, v)
                    # fold dk/dv into the carry (scaled to numerical irrelevance)
                    # so XLA cannot dead-code-eliminate their backward kernels —
                    # dropping them would time a dq-only backward
                    return (dq + 1e-30 * (dk + dv)).astype(c.dtype), None

                out, _ = jax.lax.scan(body, q, None, length=SCAN_N)
                return out

            return run

        def per_op(ms):
            return ms / SCAN_N

        xla_fwd = per_op(_time(scanned_fwd(lambda q, k, v: xla_attention(q, k, v, causal=True)), q, k, v, iters=3))
        xla_bwd = per_op(_time(scanned_bwd(lambda q, k, v: xla_attention(q, k, v, causal=True)), q, k, v, iters=3))

        table = []
        for block_q in candidates:
            for block_k in candidates:
                if seq % block_q or seq % block_k:
                    continue
                try:
                    fwd = per_op(_time(
                        scanned_fwd(
                            lambda q, k, v, bq=block_q, bk=block_k: flash_attention(
                                q, k, v, causal=True, block_q=bq, block_k=bk
                            )
                        ),
                        q, k, v, iters=3,
                    ))
                    bwd = per_op(_time(
                        scanned_bwd(
                            lambda q, k, v, bq=block_q, bk=block_k: flash_attention(
                                q, k, v, causal=True, block_q=bq, block_k=bk
                            )
                        ),
                        q, k, v, iters=3,
                    ))
                    out = flash_attention(q, k, v, causal=True, block_q=block_q, block_k=block_k)
                    ref = xla_attention(q, k, v, causal=True)
                    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32))))
                    table.append({"block_q": block_q, "block_k": block_k,
                                  "fwd_ms": round(fwd, 4), "fwdbwd_ms": round(bwd, 4),
                                  "max_err_vs_xla": err})
                    print(f"[kernels] seq={seq} bq={block_q} bk={block_k} "
                          f"fwd={fwd:.3f}ms fwd+bwd={bwd:.3f}ms", file=sys.stderr)
                except Exception as exc:
                    table.append({"block_q": block_q, "block_k": block_k,
                                  "error": f"{type(exc).__name__}: {exc}"})
                    print(f"[kernels] seq={seq} bq={block_q} bk={block_k} FAILED: {exc}",
                          file=sys.stderr)

        ok = [row for row in table if "fwdbwd_ms" in row]
        best = min(ok, key=lambda r: r["fwdbwd_ms"]) if ok else None
        results[f"b{batch}_h{heads}_s{seq}_d{head_dim}"] = {
            "xla_fwd_ms": round(xla_fwd, 4),
            "xla_fwdbwd_ms": round(xla_bwd, 4),
            "sweep": table,
            "best": best,
            "verdict": (
                "use_pallas" if best and best["fwdbwd_ms"] < xla_bwd else "use_xla"
            ) if best is not None else "pallas_failed_use_xla",
        }
    return results


def _packed_segment_ids(rng, batch, seq, segments=4, pad_frac=0.1):
    """Realistic packed rows: ``segments`` spans per row + a zero-padding suffix."""
    import numpy as np

    ids = np.zeros((batch, seq), dtype=np.int32)
    live = seq - int(seq * pad_frac)
    for b in range(batch):
        cuts = np.sort(rng.choice(np.arange(1, live), size=segments - 1, replace=False))
        bounds = np.concatenate([[0], cuts, [live]])
        for s in range(segments):
            ids[b, bounds[s] : bounds[s + 1]] = s + 1
    return ids


def sweep_packed_tpu(shapes, candidates):
    """Packed (segment-ids) pallas-vs-XLA sweep -> MEASURED_PACKED_IMPL winners.

    The structural question this answers: does the flash kernel's blockwise
    segment comparison beat the XLA path's dense (seq, seq) mask materialization?
    Output feeds ``ops/tuning.py::MEASURED_PACKED_IMPL`` (shape-class verdicts).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from unionml_tpu.ops.attention import flash_attention, xla_attention

    results = {}
    for batch, heads, seq, head_dim in shapes:
        rng = np.random.default_rng(0)
        q, k, v = (
            jnp.asarray(rng.normal(size=(batch, heads, seq, head_dim)), dtype=jnp.bfloat16)
            for _ in range(3)
        )
        seg = jnp.asarray(_packed_segment_ids(rng, batch, seq))

        SCAN_N = 32  # same on-chip amortization as the dense sweep

        def scanned_bwd(fn):
            def loss(q, k, v):
                return jnp.sum(fn(q, k, v).astype(jnp.float32) ** 2)

            grad_fn = jax.grad(loss, argnums=(0, 1, 2))

            @jax.jit
            def run(q, k, v):
                def body(c, _):
                    dq, dk, dv = grad_fn(c, k, v)
                    return (dq + 1e-30 * (dk + dv)).astype(c.dtype), None

                out, _ = jax.lax.scan(body, q, None, length=SCAN_N)
                return out

            return run

        xla_ms = _time(
            scanned_bwd(lambda q, k, v: xla_attention(q, k, v, causal=True, segment_ids=seg)),
            q, k, v, iters=3,
        ) / SCAN_N
        ref = xla_attention(q, k, v, causal=True, segment_ids=seg)  # block-size invariant

        table = []
        for block_q in candidates:
            for block_k in candidates:
                if seq % block_q or seq % block_k:
                    continue
                try:
                    ms = _time(
                        scanned_bwd(
                            lambda q, k, v, bq=block_q, bk=block_k: flash_attention(
                                q, k, v, segment_ids=seg, causal=True, block_q=bq, block_k=bk
                            )
                        ),
                        q, k, v, iters=3,
                    ) / SCAN_N
                    out = flash_attention(q, k, v, segment_ids=seg, causal=True,
                                          block_q=block_q, block_k=block_k)
                    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32))))
                    table.append({"block_q": block_q, "block_k": block_k,
                                  "fwdbwd_ms": round(ms, 4), "max_err_vs_xla": err})
                    print(f"[packed] seq={seq} bq={block_q} bk={block_k} "
                          f"fwd+bwd={ms:.3f}ms (xla {xla_ms:.3f}ms)", file=sys.stderr)
                except Exception as exc:
                    table.append({"block_q": block_q, "block_k": block_k,
                                  "error": f"{type(exc).__name__}: {exc}"})
                    print(f"[packed] seq={seq} bq={block_q} bk={block_k} FAILED: {exc}",
                          file=sys.stderr)

        ok = [row for row in table if "fwdbwd_ms" in row]
        best = min(ok, key=lambda r: r["fwdbwd_ms"]) if ok else None
        # The verdict feeds promote_tuning's PERSISTENT dispatch overlay with a
        # 2% tie margin, and merge semantics make a wrong "pallas" verdict
        # sticky — so the coarse 3-iter sweep only ranks candidates, and the
        # winner + XLA baseline are re-timed with enough samples that the
        # promoted verdict clears the margin with headroom (ADVICE round 4).
        if best is not None:
            bq, bk = best["block_q"], best["block_k"]
            xla_ms = _time(
                scanned_bwd(lambda q, k, v: xla_attention(q, k, v, causal=True, segment_ids=seg)),
                q, k, v, iters=8, reps=5,
            ) / SCAN_N
            best = dict(best)
            best["fwdbwd_ms"] = round(
                _time(
                    scanned_bwd(
                        lambda q, k, v: flash_attention(
                            q, k, v, segment_ids=seg, causal=True, block_q=bq, block_k=bk
                        )
                    ),
                    q, k, v, iters=8, reps=5,
                ) / SCAN_N,
                4,
            )
            print(f"[packed] seq={seq} verdict re-time: best bq={bq} bk={bk} "
                  f"{best['fwdbwd_ms']:.3f}ms vs xla {xla_ms:.3f}ms", file=sys.stderr)
        results[f"b{batch}_h{heads}_s{seq}_d{head_dim}"] = {
            "xla_fwdbwd_ms": round(xla_ms, 4),
            "sweep": table,
            "best": best,
            "verdict": (
                "use_pallas" if best and best["fwdbwd_ms"] < xla_ms else "use_xla"
            ) if best is not None else "pallas_failed_use_xla",
        }
    return results


def correctness_sweep_packed_cpu(shapes, candidates):
    """CPU fallback for --packed: interpret-mode correctness per block config."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from unionml_tpu.ops.attention import flash_attention, xla_attention

    results = {}
    for batch, heads, seq, head_dim in shapes:
        rng = np.random.default_rng(0)
        q, k, v = (
            jnp.asarray(rng.normal(size=(batch, heads, seq, head_dim)), dtype=jnp.float32)
            for _ in range(3)
        )
        seg = jnp.asarray(_packed_segment_ids(rng, batch, seq, segments=3))
        ref = xla_attention(q, k, v, causal=True, segment_ids=seg)
        ref_grads = jax.grad(
            lambda q, k, v: jnp.sum(xla_attention(q, k, v, causal=True, segment_ids=seg) ** 2),
            argnums=(0, 1, 2),
        )(q, k, v)
        rows = []
        for block_q in candidates:
            for block_k in candidates:
                if seq % block_q or seq % block_k:
                    continue
                out = flash_attention(q, k, v, segment_ids=seg, causal=True,
                                      block_q=block_q, block_k=block_k, interpret=True)
                err = float(jnp.max(jnp.abs(out - ref)))
                # the packed backward's block-skip bound is block-size-dependent:
                # vet dq/dk/dv per config, exactly like the dense CPU sweep
                grads = jax.grad(
                    lambda q, k, v, bq=block_q, bk=block_k: jnp.sum(
                        flash_attention(q, k, v, segment_ids=seg, causal=True,
                                        block_q=bq, block_k=bk, interpret=True) ** 2
                    ),
                    argnums=(0, 1, 2),
                )(q, k, v)
                grad_err = max(
                    float(jnp.max(jnp.abs(g - r))) for g, r in zip(grads, ref_grads)
                )
                rows.append({"block_q": block_q, "block_k": block_k, "max_err": err,
                             "max_grad_err": grad_err,
                             "ok": err < 1e-4 and grad_err < 1e-2})
        results[f"b{batch}_h{heads}_s{seq}_d{head_dim}"] = {
            "mode": "cpu-interpret-correctness-only", "sweep": rows,
            "all_ok": all(r["ok"] for r in rows),
        }
        print(f"[packed] seq={seq}: {len(rows)} block configs validated, "
              f"all_ok={all(r['ok'] for r in rows)}", file=sys.stderr)
    return results


def _paged_operands(batch, width, bs, heads, hd, quantized, dtype):
    """Pool + table + bases for one paged decode shape: each row owns ``width``
    contiguous pool blocks (plus the shared trailing scratch block) and decodes
    its last position — the steady-state serving step."""
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(0)
    blocks = batch * width + 1
    if quantized:
        k = jnp.asarray(rng.integers(-127, 128, (blocks, heads, bs, hd)), jnp.int8)
        v = jnp.asarray(rng.integers(-127, 128, (blocks, heads, bs, hd)), jnp.int8)
        ks = jnp.asarray(rng.uniform(0.005, 0.02, (blocks, heads, 1, 1)), jnp.float32)
        vs = jnp.asarray(rng.uniform(0.005, 0.02, (blocks, heads, 1, 1)), jnp.float32)
    else:
        k = jnp.asarray(rng.normal(size=(blocks, heads, bs, hd)), dtype)
        v = jnp.asarray(rng.normal(size=(blocks, heads, bs, hd)), dtype)
        ks = vs = None
    q = jnp.asarray(rng.normal(size=(batch, heads, 1, hd)), dtype)
    table = jnp.asarray(
        np.arange(batch * width, dtype=np.int32).reshape(batch, width)
    )
    base = jnp.full((batch,), width * bs - 1, jnp.int32)
    return q, k, v, table, base, ks, vs


def sweep_paged_tpu(shapes):
    """Paged-decode arm on hardware: the fused kernel, called as the engine
    calls it (it sizes its own grid), vs the XLA gather-dequant-attend arm,
    int8 AND dense pools, per pool shape."""
    import jax
    import jax.numpy as jnp

    from unionml_tpu.ops.paged_attention import (
        fused_hbm_bytes,
        gather_hbm_bytes,
        paged_attention,
    )

    SCAN_N = 64  # decode launches are microseconds: time a chained scan

    def scanned(impl):
        @jax.jit
        def run(q, k, v, table, base, ks, vs):
            def body(c, _):
                out = paged_attention(
                    c, k, v, table, base, k_scale=ks, v_scale=vs, out_dtype=c.dtype, impl=impl
                )
                return out, None

            return jax.lax.scan(body, q, None, length=SCAN_N)[0]

        return run

    results = {}
    for batch, width, bs, heads, hd in shapes:
        for quantized in (True, False):
            operands = _paged_operands(batch, width, bs, heads, hd, quantized, jnp.bfloat16)
            name = f"w{width}_bs{bs}_h{heads}_d{hd}_{'int8' if quantized else 'bf16'}"
            xla_ms = _time(scanned("xla"), *operands, iters=8, reps=5) / SCAN_N
            try:
                best = {"fwd_ms": round(_time(scanned("pallas"), *operands, iters=8, reps=5) / SCAN_N, 5)}
            except Exception as exc:  # Mosaic refused the shape
                best = None
                print(f"[paged] {name}: kernel failed: {str(exc)[:200]}", file=sys.stderr)
            results[name] = {
                "xla_fwd_ms": round(xla_ms, 5),
                "best": best,
                "verdict": (
                    "use_pallas" if best["fwd_ms"] < xla_ms else "use_xla"
                ) if best is not None else "pallas_failed_use_xla",
                "fused_hbm_bytes": fused_hbm_bytes(width, bs, heads, hd, quantized),
                "gather_hbm_bytes": gather_hbm_bytes(width, bs, heads, hd, quantized),
            }
            print(f"[paged] {name}: xla {xla_ms:.5f}ms kernel "
                  f"{best['fwd_ms'] if best else float('nan'):.5f}ms "
                  f"-> {results[name]['verdict']}", file=sys.stderr)
    return results


def correctness_sweep_paged_cpu(shapes):
    """CPU fallback for --paged: interpret-mode parity of the kernel, both pool
    dtypes, against the XLA gather reference."""
    import jax.numpy as jnp

    from unionml_tpu.ops.paged_attention import (
        fused_hbm_bytes,
        gather_hbm_bytes,
        paged_attention,
    )

    results = {}
    for batch, width, bs, heads, hd in shapes:
        for quantized in (True, False):
            q, k, v, table, base, ks, vs = _paged_operands(
                batch, width, bs, heads, hd, quantized, jnp.float32
            )
            name = f"w{width}_bs{bs}_h{heads}_d{hd}_{'int8' if quantized else 'f32'}"
            args = dict(k_scale=ks, v_scale=vs, out_dtype=jnp.float32)
            ref = paged_attention(q, k, v, table, base, impl="xla", **args)
            out = paged_attention(q, k, v, table, base, impl="pallas", interpret=True, **args)
            err = float(jnp.max(jnp.abs(out - ref)))
            results[name] = {
                "mode": "cpu-interpret-correctness-only",
                "max_err": err,
                "all_ok": err < 1e-4,
                "fused_hbm_bytes": fused_hbm_bytes(width, bs, heads, hd, quantized),
                "gather_hbm_bytes": gather_hbm_bytes(width, bs, heads, hd, quantized),
            }
            print(f"[paged] {name}: all_ok={results[name]['all_ok']}", file=sys.stderr)
    return results


def gate_paged_traffic(shapes):
    """ISSUE-18 acceptance gate: the fused kernel's modeled HBM bytes/step must
    be EXACTLY the stored codes + scales — the dense gather copy provably gone
    from the traffic model. Returns the gate rows; raises SystemExit on excess."""
    from unionml_tpu.ops.paged_attention import fused_hbm_bytes, gather_hbm_bytes

    rows = []
    for batch, width, bs, heads, hd in shapes:
        for quantized in (True, False):
            kv_positions = 2 * width * bs * heads * hd
            codes = kv_positions * (1 if quantized else 2)
            scales = 2 * width * heads * 4 if quantized else 0
            fused = fused_hbm_bytes(width, bs, heads, hd, quantized)
            rows.append({
                "width": width, "block_size": bs, "heads": heads, "head_dim": hd,
                "quantized": quantized, "fused_hbm_bytes": fused,
                "codes_plus_scales": codes + scales,
                "gather_hbm_bytes": gather_hbm_bytes(width, bs, heads, hd, quantized),
            })
            if fused > codes + scales:
                print(f"[paged] TRAFFIC GATE FAILED: fused model reads {fused} "
                      f"bytes/step but codes+scales are {codes + scales} "
                      f"(w={width} bs={bs} h={heads} d={hd} int8={quantized})",
                      file=sys.stderr)
                raise SystemExit(1)
    return rows


def correctness_sweep_cpu(shapes, candidates):
    """CPU fallback: validate every block config numerically in interpret mode."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from unionml_tpu.ops.attention import flash_attention, xla_attention

    results = {}
    for batch, heads, seq, head_dim in shapes:
        rng = np.random.default_rng(0)
        q, k, v = (
            jnp.asarray(rng.normal(size=(batch, heads, seq, head_dim)), dtype=jnp.float32)
            for _ in range(3)
        )
        ref = xla_attention(q, k, v, causal=True)
        ref_grads = jax.grad(
            lambda q, k, v: jnp.sum(xla_attention(q, k, v, causal=True) ** 2), argnums=(0, 1, 2)
        )(q, k, v)
        rows = []
        for block_q in candidates:
            for block_k in candidates:
                if seq % block_q or seq % block_k:
                    continue
                out = flash_attention(q, k, v, causal=True, block_q=block_q, block_k=block_k,
                                      interpret=True)
                err = float(jnp.max(jnp.abs(out - ref)))
                # backward kernels are block-size-dependent too: vet them per config
                grads = jax.grad(
                    lambda q, k, v, bq=block_q, bk=block_k: jnp.sum(
                        flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk,
                                        interpret=True) ** 2
                    ),
                    argnums=(0, 1, 2),
                )(q, k, v)
                grad_err = max(
                    float(jnp.max(jnp.abs(g - r))) for g, r in zip(grads, ref_grads)
                )
                rows.append({"block_q": block_q, "block_k": block_k, "max_err": err,
                             "max_grad_err": grad_err,
                             "ok": err < 1e-4 and grad_err < 1e-2})
        results[f"b{batch}_h{heads}_s{seq}_d{head_dim}"] = {
            "mode": "cpu-interpret-correctness-only", "sweep": rows,
            "all_ok": all(r["ok"] for r in rows),
        }
        print(f"[kernels] seq={seq}: {len(rows)} block configs validated, "
              f"all_ok={all(r['ok'] for r in rows)}", file=sys.stderr)
    return results


def main():
    import jax

    packed_mode = "--packed" in sys.argv
    paged_mode = "--paged" in sys.argv
    backend = jax.default_backend()
    # BERT-base fine-tune shapes + mid/long sequences + a head_dim-128 family
    # (GPT-2 context at 1024; 128-dim heads cover larger decoder configs)
    shapes = [
        (8, 12, 128, 64),
        (4, 12, 256, 64),
        (4, 12, 512, 64),
        (2, 12, 1024, 64),
        (2, 16, 512, 128),
    ]
    candidates = (128, 256, 512)

    if paged_mode:
        # paged decode pool shapes (batch, table_width, block_size, heads, head_dim):
        # pool-size sweep over the table width at serving-typical head layouts
        paged_shapes = [
            (8, 8, 16, 12, 64),
            (8, 16, 16, 12, 64),
            (8, 32, 16, 12, 64),
            (4, 16, 16, 16, 128),
        ]
        if backend == "cpu":
            paged_shapes = [(2, 4, 4, 2, 16), (2, 6, 4, 4, 16)]
            results = correctness_sweep_paged_cpu(paged_shapes)
            payload = {"backend": backend, "timing_valid": False, "results": results}
        else:
            results = sweep_paged_tpu(paged_shapes)
            payload = {"backend": backend, "timing_valid": True, "results": results}
        # the acceptance gate runs in BOTH modes: the traffic model is static
        payload["traffic_gate"] = gate_paged_traffic(paged_shapes)
        out_path, metric = "PAGED_KERNEL_BENCH.json", "paged_kernel_sweep"
    elif packed_mode:
        # packed training shapes (GPT: causal + segment ids)
        shapes = [(8, 12, 128, 64), (4, 12, 512, 64), (2, 12, 1024, 64)]
        if backend == "cpu":
            shapes = [(2, 2, 128, 64)]
            results = correctness_sweep_packed_cpu(shapes, candidates)
            payload = {"backend": backend, "timing_valid": False, "results": results}
        else:
            results = sweep_packed_tpu(shapes, candidates)
            payload = {"backend": backend, "timing_valid": True, "results": results}
        out_path, metric = "PACKED_KERNEL_BENCH.json", "packed_kernel_sweep"
    elif backend == "cpu":
        shapes = [(2, 2, 128, 64), (1, 2, 256, 64)]  # interpret mode is slow
        results = correctness_sweep_cpu(shapes, candidates)
        payload = {"backend": backend, "timing_valid": False, "results": results}
        out_path, metric = "KERNEL_BENCH.json", "kernel_sweep"
    else:
        results = sweep_tpu(shapes, candidates)
        payload = {"backend": backend, "timing_valid": True, "results": results}
        out_path, metric = "KERNEL_BENCH.json", "kernel_sweep"

    payload["recorded_at"] = datetime.now(timezone.utc).isoformat(timespec="seconds")
    from bench_util import resolve_artifact_path

    out_path = resolve_artifact_path(out_path, backend)
    with open(out_path, "w") as fh:
        json.dump(payload, fh, indent=2)
    print(json.dumps({"metric": metric, "backend": backend,
                      "timing_valid": payload["timing_valid"],
                      "shapes": len(results), "artifact": out_path}))


if __name__ == "__main__":
    main()

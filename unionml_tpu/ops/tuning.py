"""Flash-attention block-size selection.

Mosaic tile choice is a measured quantity, not a guess: ``bench_kernels.py`` sweeps
``(block_q, block_k)`` on real hardware and records winners per shape class in
``KERNEL_BENCH.json`` at the repo root; the committed winners live in
:data:`TUNED_BLOCKS` below. Shapes without a measured entry fall back to the largest
candidate block that tiles the sequence (<= 128 until measurements justify bigger:
the guess is explicit, bounded, and overridden by data as it lands).

Shape class key: ``(seq_q, seq_k, head_dim)``.
"""

from typing import Dict, Tuple

#: measured winners — populated from bench_kernels.py runs on real TPU hardware.
#: Format: {(seq_q, seq_k, head_dim): (block_q, block_k)}
TUNED_BLOCKS: Dict[Tuple[int, int, int], Tuple[int, int]] = {
    # Measured on v5e via the ON-DEVICE scanned sweep (KERNEL_BENCH.json,
    # 2026-07-29; bench_kernels.py has the methodology note).
    (128, 128, 64): (128, 128),
    (256, 256, 64): (256, 256),
    (512, 512, 64): (256, 512),
    (1024, 1024, 64): (512, 512),
    (512, 512, 128): (512, 512),
}

#: measured pallas-vs-XLA verdicts per shape class (same sweep, plus a BERT-base
#: train step at B=64 S=128 that ran faster with XLA attention end to end).
#: XLA's fused attention won or tied every measured shape on v5e; the pallas
#: kernels remain available via impl="pallas" and carry the tuned blocks above.
MEASURED_IMPL: Dict[Tuple[int, int, int], str] = {
    (128, 128, 64): "xla",
    (256, 256, 64): "xla",
    (512, 512, 64): "xla",
    (1024, 1024, 64): "xla",  # sweep margin <1% — a tie broken toward the default
    (512, 512, 128): "xla",
}

#: unmeasured shapes follow the measured trend on this hardware
DEFAULT_TPU_IMPL = "xla"


def pick_impl(seq_q: int, seq_k: int, head_dim: int) -> str:
    """Measured attention backend for a shape class ("xla" or "pallas")."""
    return MEASURED_IMPL.get((seq_q, seq_k, head_dim), DEFAULT_TPU_IMPL)


#: measured pallas-vs-XLA verdicts for PACKED (segment-ids) shapes. The regimes
#: differ structurally from the dense case: the XLA path must materialize a dense
#: (seq, seq) mask per row (O(seq^2) HBM write + read), while the kernel compares
#: segment ids blockwise in VMEM. Populated from ``bench_kernels.py --packed``
#: runs on real hardware (PACKED_KERNEL_BENCH.json).
MEASURED_PACKED_IMPL: Dict[Tuple[int, int, int], str] = {}

#: unmeasured packed shapes follow the measured dense-shape trend (XLA wins or
#: ties every measured practical shape on v5e). The kernel's structural edge —
#: no dense O(seq^2) mask — is plausible but UNMEASURED; an unmeasured default
#: must be the conservative one. A ``--packed`` sweep flips this per shape class.
DEFAULT_PACKED_IMPL = "xla"


def pick_packed_impl(seq_q: int, seq_k: int, head_dim: int) -> str:
    """Measured attention backend for a packed (segment-ids) shape class."""
    return MEASURED_PACKED_IMPL.get((seq_q, seq_k, head_dim), DEFAULT_PACKED_IMPL)


#: measured winners for PACKED (segment-ids) sweeps — kept separate from the
#: dense table: the segment-masked, block-skipping kernel has its own optimal
#: tiling, and a packed winner must never displace a dense one (or vice versa)
PACKED_TUNED_BLOCKS: Dict[Tuple[int, int, int], Tuple[int, int]] = {}

#: candidate block edges for the sweep and the fallback ladder
BLOCK_CANDIDATES: Tuple[int, ...] = (512, 256, 128, 64)

#: measured pallas-vs-XLA verdicts for the PAGED decode kernel
#: (:mod:`unionml_tpu.ops.paged_attention`). Shape class:
#: ``(table_width, block_size, heads, row)``, ``row`` the last dimension of the
#: call's pool leaf (``2 * head_dim`` over the joined full-precision leaf). The kernel's own tiling (heads
#: and table entries a grid step) is no table here: ``paged_attention._tiling``
#: reckons it from the call's shapes. An entry is an explicit verdict: "xla" where
#: the kernel lost a ``bench_kernels.py --paged`` sweep, or where Mosaic refused
#: to compile the shape (the compiler's message goes beside the entry). There
#: is no runtime fallback between the arms — this table is the only way a
#: shape leaves the kernel. On the v5e, chip_smoke.py compiles and checks the
#: kernel at GPT-2 small's classes (65, 16, 12, 128 | 64) and GPT-2 medium's
#: (65, 16, 16, 128 | 64) over bf16 and int8 pools: all run it, so the table is empty.
MEASURED_PAGED_IMPL: Dict[Tuple[int, int, int, int], str] = {}

#: unmeasured paged shapes default to the KERNEL — deliberately the opposite of
#: the conservative dense default: the XLA arm's dense dequantized gather copy
#: is a modeled ~4x HBM write+read the kernel structurally never issues
#: (``paged_attention.gather_hbm_bytes`` vs ``fused_hbm_bytes``), so here the
#: burden of proof sits on XLA; a measured window demotes per shape class.
DEFAULT_PAGED_IMPL = "pallas"


def pick_paged_impl(table_width: int, block_size: int, heads: int, head_dim: int) -> str:
    """Measured paged-decode backend for a shape class ("pallas" or "xla")."""
    return MEASURED_PAGED_IMPL.get(
        (table_width, block_size, heads, head_dim), DEFAULT_PAGED_IMPL
    )


def _largest_dividing(seq: int, cap: int = 128) -> int:
    for candidate in BLOCK_CANDIDATES:
        if candidate <= cap and seq % candidate == 0:
            return candidate
    if seq <= cap and seq % 8 == 0:
        return seq  # tiny but Mosaic-tileable (sublane multiple): one block
    # irregular or unalignable-at-cap sequences (seq % cap != 0 is guaranteed here —
    # a dividing cap would have been returned by the candidate loop): return the
    # non-dividing cap so the kernel's alignment check routes the call to the XLA
    # fallback instead of a doomed Mosaic compile (or a seq x seq tile over VMEM)
    return cap


def pick_block_sizes(
    seq_q: int, seq_k: int, head_dim: int, packed: bool = False
) -> Tuple[int, int]:
    """Block sizes for a flash-attention call: measured winner, else aligned default.

    ``packed=True`` consults the packed sweep's winners first (falling back to
    the dense winners, then the aligned ladder).
    """
    shape = (seq_q, seq_k, head_dim)
    if packed:
        tuned = PACKED_TUNED_BLOCKS.get(shape) or TUNED_BLOCKS.get(shape)
    else:
        tuned = TUNED_BLOCKS.get(shape)
    if tuned is not None:
        return tuned
    return _largest_dividing(seq_q), _largest_dividing(seq_k)


def _apply_measured_overlay() -> None:
    """Merge ``TUNING_MEASURED.json`` (repo root) over the static tables.

    ``tools/promote_tuning.py`` distills kernel-sweep artifacts into this one
    overlay file, so a hardware run updates the dispatch tables without
    hand-editing source. No overlay is committed today: the static tables
    above are the whole verdict. Key format: ``"seq_q,seq_k,head_dim"``.
    """
    import json
    import os

    # Explicit env-var hook first, then the repo root (developer checkout). No
    # cwd fallback: a stale TUNING_MEASURED.json in an unrelated working
    # directory must not silently alter kernel dispatch (ADVICE round 4).
    candidates = [
        os.environ.get("UNIONML_TUNING_OVERLAY", ""),
        os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "..", "TUNING_MEASURED.json"),
    ]
    overlay = None
    for path in candidates:
        if not path:
            continue
        try:
            with open(path) as fh:
                loaded = json.load(fh)
        except (OSError, ValueError):
            continue
        # valid JSON of the wrong type is as malformed as broken syntax: fall
        # through to the next candidate either way
        if isinstance(loaded, dict):
            overlay = loaded
            break
    if overlay is None:
        return

    def parse(table, rank=3):
        out = {}
        if not isinstance(table, dict):
            return out
        for key, value in table.items():
            try:
                shape = tuple(int(x) for x in key.split(","))
            except (AttributeError, ValueError):
                continue
            if len(shape) == rank:
                out[shape] = value
        return out

    def valid_impl(value):
        return value in ("xla", "pallas")

    def valid_blocks(value):
        return (
            isinstance(value, (list, tuple))
            and len(value) == 2
            and all(isinstance(b, int) and not isinstance(b, bool) and b > 0 for b in value)
        )

    # Malformed entries (wrong type, unknown impl, non-int blocks) are dropped
    # here rather than surfacing later as a confusing in-trace failure.
    for shape, impl in parse(overlay.get("measured_impl")).items():
        if valid_impl(impl):
            MEASURED_IMPL[shape] = impl
    for shape, impl in parse(overlay.get("measured_packed_impl")).items():
        if valid_impl(impl):
            MEASURED_PACKED_IMPL[shape] = impl
    for shape, blocks in parse(overlay.get("tuned_blocks")).items():
        if valid_blocks(blocks):
            TUNED_BLOCKS[shape] = tuple(blocks)
    for shape, blocks in parse(overlay.get("packed_tuned_blocks")).items():
        if valid_blocks(blocks):
            PACKED_TUNED_BLOCKS[shape] = tuple(blocks)
    # paged-decode kernel verdicts: 4-axis keys "table_width,block_size,heads,head_dim".
    # (The kernel has no tiling table: it sizes its grid from the shapes it is
    # called with. An older overlay's "paged_tuned_heads" is ignored, like any
    # other key this function does not know.)
    for shape, impl in parse(overlay.get("measured_paged_impl"), rank=4).items():
        if valid_impl(impl):
            MEASURED_PAGED_IMPL[shape] = impl


_apply_measured_overlay()

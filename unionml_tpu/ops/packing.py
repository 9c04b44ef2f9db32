"""Sequence packing: several short sequences per training row.

Short-sequence corpora waste most of a fixed-shape batch on padding (a 40-token
example in a 512-token row computes 92% padding). Packing concatenates sequences
into rows and carries ``segment_ids`` so attention stays confined to each
sequence (``ops.attention`` masks cross-segment pairs blockwise in the flash
kernel — no dense (seq, seq) mask) and positions restart per segment
(``models/gpt.py::GPTLMHeadModel``).

This is a capability the reference cannot express at all: its training loop is
whatever the user's ``@model.trainer`` does with torch/sklearn, with no packing
support anywhere (reference ``unionml/dataset.py`` hands frames to user code).

Convention (t5x/flax): segment id 0 = padding, 1..n = packed sequences, ids
restart from 1 in every row. Static shapes throughout — rows are (seq_len,)
always, so one XLA program serves every packed batch.
"""

from typing import Dict, List, Sequence

import numpy as np

__all__ = ["pack_sequences", "packing_efficiency"]


#: corpora at least this large route to the native packer under impl="auto":
#: below it the ctypes marshalling overhead rivals the Python loop's cost
NATIVE_PACK_THRESHOLD = 2048


def pack_sequences(
    sequences: Sequence[np.ndarray],
    seq_len: int,
    *,
    pad_id: int = 0,
    max_segments_per_row: int = 0,
    impl: str = "auto",
) -> Dict[str, np.ndarray]:
    """Greedy first-fit packing of token sequences into fixed-length rows.

    :param sequences: 1-D int token arrays (ragged lengths). Sequences longer
        than ``seq_len`` are truncated to ``seq_len`` (logged in the result's
        ``truncated`` count rather than silently).
    :param seq_len: the packed row length (the compiled program's static shape).
    :param pad_id: token id written into padding slots.
    :param max_segments_per_row: cap on sequences per row (0 = unlimited) — some
        objectives want to bound the in-row mixing.
    :param impl: ``"python"``, ``"native"`` (C++ via
        :func:`unionml_tpu.native.pack_sequences_native`; falls back to Python
        when the toolchain is absent), or ``"auto"`` — native for corpora of
        ``NATIVE_PACK_THRESHOLD``+ sequences. Both paths run the SAME first-fit
        algorithm and produce byte-identical outputs (pinned by tests); native
        exists because the Python loop's O(n_seqs x n_rows) interpreter cost
        dominates job start-up at corpus scale.
    :returns: dict with ``input_ids`` (rows, seq_len) int32, ``segment_ids``
        (rows, seq_len) int32 (0 = padding), ``positions`` (rows, seq_len) int32
        (restarting per segment), and ``truncated`` (int) — how many input
        sequences lost tokens to the ``seq_len`` cap.
    """
    if seq_len <= 0:
        raise ValueError(f"seq_len must be positive, got {seq_len}")
    if impl not in ("auto", "python", "native"):
        raise ValueError(f"impl must be 'auto', 'python', or 'native', got {impl!r}")

    # normalize once, shared by both paths: drop empties, truncate overlong
    arrays: List[np.ndarray] = []
    truncated = 0
    for seq in sequences:
        arr = np.asarray(seq).reshape(-1)
        if arr.size == 0:
            continue
        if arr.size > seq_len:
            arr = arr[:seq_len]
            truncated += 1
        arrays.append(arr)

    want_native = impl == "native" or (impl == "auto" and len(arrays) >= NATIVE_PACK_THRESHOLD)
    if want_native:
        from unionml_tpu.native import pack_sequences_native

        lengths = np.asarray([a.size for a in arrays], dtype=np.int64)
        flat = (
            np.concatenate([a.astype(np.int32, copy=False) for a in arrays])
            if arrays
            else np.empty((0,), dtype=np.int32)
        )
        packed = pack_sequences_native(flat, lengths, seq_len, pad_id, max_segments_per_row)
        if packed is not None:
            packed["truncated"] = truncated
            return packed
        # no toolchain: fall through to the Python path

    rows: List[List[np.ndarray]] = []
    row_space: List[int] = []
    row_segments: List[int] = []
    for arr in arrays:
        placed = False
        # first-fit: the earliest row with room (and segment headroom)
        for i in range(len(rows)):
            if row_space[i] >= arr.size and (
                max_segments_per_row <= 0 or row_segments[i] < max_segments_per_row
            ):
                rows[i].append(arr)
                row_space[i] -= arr.size
                row_segments[i] += 1
                placed = True
                break
        if not placed:
            rows.append([arr])
            row_space.append(seq_len - arr.size)
            row_segments.append(1)

    n_rows = max(len(rows), 1)
    input_ids = np.full((n_rows, seq_len), pad_id, dtype=np.int32)
    segment_ids = np.zeros((n_rows, seq_len), dtype=np.int32)
    positions = np.zeros((n_rows, seq_len), dtype=np.int32)
    for r, row in enumerate(rows):
        offset = 0
        for s, arr in enumerate(row, start=1):
            end = offset + arr.size
            input_ids[r, offset:end] = arr
            segment_ids[r, offset:end] = s
            positions[r, offset:end] = np.arange(arr.size)
            offset = end
    return {
        "input_ids": input_ids,
        "segment_ids": segment_ids,
        "positions": positions,
        "truncated": truncated,
    }


def packing_efficiency(segment_ids: np.ndarray) -> float:
    """Fraction of token slots carrying real tokens (1.0 = no padding at all)."""
    total = segment_ids.size
    return float((np.asarray(segment_ids) > 0).sum()) / total if total else 0.0

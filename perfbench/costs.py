"""Operations and bytes a GPT-2 step needs, from its published sizes alone.

These count what the algorithm requires, not what an implementation does: a
kernel that walks its whole table, recomputes, or pads still gets only the
live work credited, so its share of the roofline falls.
"""

from __future__ import annotations

from typing import Dict, Iterable


def matmul_params(sizes: Dict[str, int], tied_head: bool = True) -> int:
    """Weights that take part in a matrix multiplication for every token: the
    blocks' four dense layers and the head (tied to the token embedding).
    Embedding look-ups, biases and LayerNorms are not multiplications."""
    d = sizes["n_embd"]
    inner = sizes.get("n_inner") or 4 * d
    per_layer = d * 3 * d + d * d + d * inner + inner * d
    head = sizes["vocab_size"] * d if tied_head else 0
    return sizes["n_layer"] * per_layer + head


def attention_flops(sizes: Dict[str, int], keys: float) -> float:
    """Forward FLOPs of one query token's attention over ``keys`` keys, all
    layers: QK^T and PV, 2 FLOPs a multiply-add, over the full hidden width."""
    return sizes["n_layer"] * 4.0 * keys * sizes["n_embd"]


def decode_flops(sizes: Dict[str, int], live_lengths: Iterable[float]) -> float:
    """Forward FLOPs of decode steps that advance one row per entry of
    ``live_lengths`` (the keys that row attends over, its new token included)."""
    dense = 2.0 * matmul_params(sizes)
    return sum(dense + attention_flops(sizes, keys) for keys in live_lengths)


def paged_attention_bytes(
    sizes: Dict[str, int], live_lengths: Iterable[float], kv_bytes: float, act_bytes: float
) -> float:
    """Bytes decode attention has to move for those rows, all layers: each
    row's live K and V once, its query in and its output out."""
    d = sizes["n_embd"]
    per_key = 2.0 * d * kv_bytes
    per_row = 2.0 * d * act_bytes
    return sizes["n_layer"] * sum(keys * per_key + per_row for keys in live_lengths)


def train_flops_per_token(sizes: Dict[str, int], mean_keys: float) -> float:
    """Forward and backward FLOPs a trained token needs: 6 per matmul weight
    (2 forward, 4 backward) and three times the forward attention over the
    ``mean_keys`` keys a token sees on average under the causal, per-document
    mask. Recomputation is not counted."""
    return 6.0 * matmul_params(sizes) + 3.0 * attention_flops(sizes, mean_keys)


def mean_causal_keys(document_lengths: Iterable[int]) -> float:
    """Keys per token, averaged over the tokens of those documents: a token at
    position ``i`` of its document sees ``i + 1`` keys."""
    tokens = keys = 0
    for n in document_lengths:
        tokens += n
        keys += n * (n + 1) // 2
    return keys / tokens if tokens else 0.0

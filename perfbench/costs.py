"""What the counts of every family share (a family's own operations and bytes
are in ``families/<family>.py``)."""

from __future__ import annotations

from typing import Iterable


def mean_causal_keys(document_lengths: Iterable[int]) -> float:
    """Keys per token, averaged over the tokens of those documents: a token at
    position ``i`` of its document sees ``i + 1`` keys."""
    tokens = keys = 0
    for n in document_lengths:
        tokens += n
        keys += n * (n + 1) // 2
    return keys / tokens if tokens else 0.0

"""The ``toy`` family's plain reference. The family's equations are the GPT-2
decoder's, so this reads them from ``perfbench/reference/gpt2.py`` and brings
them under this family's own argument names. Imports nothing of the program."""

from perfbench.reference import gpt2

adamw_step = gpt2.adamw_step


def logits_at(params, ids, rows, lowp=None, *, heads, epsilon):
    return gpt2.logits_at(params, ids, rows, num_heads=heads, eps=epsilon, lowp=lowp)


def loss_and_grads(params, ids, segments, lowp=None, *, heads, epsilon):
    return gpt2.loss_and_grads(params, ids, segments, num_heads=heads, eps=epsilon, lowp=lowp)

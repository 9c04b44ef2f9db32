"""The seed as a PRNG key: every family draws its weights from this, on the
device, in one jitted call (``families/<family>.py::make_params``)."""

from __future__ import annotations

import jax


def seed_key(seed: int, stream: int = 0) -> jax.Array:
    """A PRNG key from any non-negative whole-number seed (over 2**31 too)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    key = jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)
    return jax.random.fold_in(key, stream)

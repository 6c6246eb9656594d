"""Seeded random weights, made on the device: what every family's
``engine_params`` and reference share.

The benchmark serves random weights: they are enough for speed and for
agreement with the reference.  A family (``families/<name>.py``) makes its
whole parameter tree from ``seed_key(seed, WEIGHTS)`` in one jitted call, in
the served dtype, and its reference rebuilds the same values one layer at a
time after the program's state is freed.

Scales differ from the program's own init on purpose: biases and norm
weights are non-zero so their paths are checked, and the output head gives
logits with a standard deviation of about ``LOGIT_STD``, so the top tokens
are neither tied nor far apart.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

BIAS_STD = 0.5
NORM_JITTER = 0.1
LOGIT_STD = 3.0


def seed_key(seed: int, purpose: int) -> jax.Array:
    """A raw threefry key from any whole-number ``seed`` (no 32-bit limit)
    and a ``purpose`` tag, so weights and traffic draw independent streams."""
    words = np.random.SeedSequence([int(seed), int(purpose)]).generate_state(2)
    return jnp.asarray(words, dtype=jnp.uint32)


WEIGHTS = 1


def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def _norm(key, n, dtype):
    return (1.0 + NORM_JITTER * jax.random.normal(key, (n,), jnp.float32)
            ).astype(dtype)

"""What every family's plain float32 reference shares, and the comparison
of its logits with the tokens the program served, which decides ``correct``.

A family's ``Reference`` (``families/<name>.py``) is written from the
architecture's published description.  It imports nothing of the program
and takes nothing the program made: the weights come again from the seed,
one layer at a time, so the whole model never has to sit on the device in
float32.  Matrix products run at ``highest`` precision, and sequences pad to
a power of two from ``MIN_PAD`` so few shapes compile.

``quant`` makes the control: the same pass with every linear layer's
weights (per output channel) and inputs (per token) rounded by a function of
``ROUNDERS``; ``fp8`` is float8 e4m3, the precision below the served
bfloat16.  The control is put in the program's place: ``greedy_tokens`` and
``nucleus_tokens`` give what it would serve, and the same comparison judges
it.
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

MIN_PAD = 256      # sequences pad to a power of two from here: few shapes


def _round_fp8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


ROUNDERS = {"fp8": _round_fp8}


def served_gaps(ref_logits: jax.Array, served: Sequence[int]) -> np.ndarray:
    """How far below the reference's best logit each served token lies."""
    lg = ref_logits
    tok = jnp.asarray(np.asarray(served, np.int32))
    chosen = jnp.take_along_axis(lg, tok[:, None], axis=-1)[:, 0]
    return np.asarray(jnp.max(lg, axis=-1) - chosen)


def greedy_tokens(logits: jax.Array) -> np.ndarray:
    """The token each row puts first: what a greedy request is served."""
    return np.asarray(jnp.argmax(logits, -1))


@jax.jit
def _mass_above(logits, tok, temperature):
    z = logits / temperature
    p = jax.nn.softmax(z, axis=-1)
    chosen = jnp.take_along_axis(z, tok[:, None], axis=-1)
    return jnp.sum(jnp.where(z > chosen, p, 0.0), axis=-1)


def nucleus_excess(ref_logits: jax.Array, served: Sequence[int],
                   temperature: float, top_p: float) -> np.ndarray:
    """How far each served token lies outside the reference's nucleus: the
    reference's probability mass (at ``temperature``) on tokens it ranks
    above the served one, less ``top_p``.  A top-p sampler only serves
    tokens with less than ``top_p`` of the mass above them, so a sound
    program reads at most the rounding of that mass."""
    tok = jnp.asarray(np.asarray(served, np.int32))
    return np.asarray(_mass_above(ref_logits, tok, temperature)) - top_p


@jax.jit
def _nucleus_draw(logits, u, temperature, top_p):
    z = logits / temperature
    order = jnp.argsort(-z, axis=-1)
    p = jax.nn.softmax(jnp.take_along_axis(z, order, axis=-1), axis=-1)
    before = jnp.cumsum(p, axis=-1) - p
    p = jnp.where(before < top_p, p, 0.0)
    cum = jnp.cumsum(p, axis=-1)
    rank = jnp.sum(cum < u[:, None] * cum[:, -1:], axis=-1)
    rank = jnp.minimum(rank, logits.shape[-1] - 1)
    return jnp.take_along_axis(order, rank[:, None], axis=-1)[:, 0]


def nucleus_tokens(logits: jax.Array, u: np.ndarray, temperature: float,
                   top_p: float) -> np.ndarray:
    """What a top-p sampler serves from ``logits``: for each row, the token
    at the uniform draw ``u`` of its nucleus (the highest-ranked tokens with
    less than ``top_p`` of the mass above each), by inverse CDF."""
    return np.asarray(_nucleus_draw(logits, jnp.asarray(u, jnp.float32),
                                    temperature, top_p))

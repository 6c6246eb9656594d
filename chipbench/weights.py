"""Seeded random weights for a Qwen2 configuration, made on the device.

The benchmark serves random weights: they are enough for speed and for
agreement with the reference.  ``engine_params`` makes the whole parameter
tree in the layout ``repro.models.model.Model`` reads, in the served dtype,
in one jitted call.  ``layer_weights`` and ``table_weights`` make the same
values one layer at a time, so the reference can rebuild them layer by
layer after the program's state is freed.

Scales differ from the program's own init on purpose: q/k/v biases and norm
weights are non-zero so their paths are checked, and the output head gives
logits with a standard deviation of about 3, so the top tokens are neither
tied nor far apart.

With tied embeddings the head is the embedding table, and the usual recipe
(a small table, a final norm gain near 1) makes greedy outputs collapse: the
layers' input-independent parts (biases, averaged values) swamp the small
token embeddings, and every position predicts one token, so no precision
error can flip a served token.  Tied tables are therefore +-1 entries, which
keep the token in the residual stream, and the final norm gain has zero sum
and carries the logit scale: a token's logit for itself is then
``sum(gain * e * e) = sum(gain) = 0``, so it predicts neither itself nor a
constant.
"""
from __future__ import annotations

import math

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


def _dims(dims: dict):
    d = dims["hidden_size"]
    h, hkv = dims["num_attention_heads"], dims["num_key_value_heads"]
    hd = dims.get("head_dim") or d // h
    return d, h, hkv, hd, dims["intermediate_size"]


def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def _norm(key, n, dtype):
    return (1.0 + NORM_JITTER * jax.random.normal(key, (n,), jnp.float32)
            ).astype(dtype)


def layer_weights(key: jax.Array, dims: dict, dtype) -> dict:
    """One decoder layer, in the program's per-layer layout."""
    d, h, hkv, hd, dff = _dims(dims)
    k = jax.random.split(key, 12)
    return {
        "attn": {
            "ln": _norm(k[0], d, dtype),
            "wq": _normal(k[1], (d, h * hd), 1 / math.sqrt(d), dtype),
            "wk": _normal(k[2], (d, hkv * hd), 1 / math.sqrt(d), dtype),
            "wv": _normal(k[3], (d, hkv * hd), 1 / math.sqrt(d), dtype),
            "wo": _normal(k[4], (h * hd, d), 1 / math.sqrt(h * hd), dtype),
            "bq": _normal(k[5], (h * hd,), BIAS_STD, dtype),
            "bk": _normal(k[6], (hkv * hd,), BIAS_STD, dtype),
            "bv": _normal(k[7], (hkv * hd,), BIAS_STD, dtype),
        },
        "ffn_ln": _norm(k[8], d, dtype),
        "ffn": {
            "w_gate": _normal(k[9], (d, dff), 1 / math.sqrt(d), dtype),
            "w_up": _normal(k[10], (d, dff), 1 / math.sqrt(d), dtype),
            "w_down": _normal(k[11], (dff, d), 1 / math.sqrt(dff), dtype),
        },
    }


def table_weights(key: jax.Array, dims: dict, dtype) -> dict:
    """Embedding, final norm and (untied) output head."""
    d, v = dims["hidden_size"], dims["vocab_size"]
    k = jax.random.split(key, 3)
    head_std = LOGIT_STD / math.sqrt(d)
    out = {"final_ln": _norm(k[1], d, dtype)}
    if dims["tie_word_embeddings"]:
        signs = jax.random.bernoulli(k[0], 0.5, (v, d))
        out["embed"] = jnp.where(signs, 1.0, -1.0).astype(dtype)
        g = jax.random.normal(k[1], (d,), jnp.float32)
        out["final_ln"] = ((g - g.mean()) * head_std).astype(dtype)
    else:
        out["embed"] = _normal(k[0], (v, d), 1.0, dtype)
        out["lm_head"] = _normal(k[2], (d, v), head_std, dtype)
    return out


def layer_key(base: jax.Array, i: int) -> jax.Array:
    return jax.random.fold_in(base, i + 1)


def engine_params(seed: int, dims: dict) -> dict:
    """The served parameter tree, made on the default device in one jitted
    call."""
    dtype = jnp.dtype(dims["torch_dtype"])
    n = dims["num_hidden_layers"]

    def make(base):
        keys = jnp.stack([layer_key(base, i) for i in range(n)])
        params = table_weights(jax.random.fold_in(base, 0), dims, dtype)
        params["prefix_layers"] = []
        params["block"] = {"pos0": jax.vmap(
            lambda kk: layer_weights(kk, dims, dtype))(keys)}
        return params

    return jax.jit(make)(seed_key(seed, WEIGHTS))

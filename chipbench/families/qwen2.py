"""Qwen2 (arXiv:2407.10671; the Hugging Face ``Qwen2`` modelling code): what
the harness needs of this architecture, found by ``cell.family`` for a
configuration whose ``architectures[0]`` is ``Qwen2ForCausalLM``.

The architecture: pre-norm decoder layers of RMSNorm, grouped-query
self-attention with q/k/v biases and rotary embedding (rotate-half form,
base ``rope_theta``), RMSNorm and a SwiGLU MLP; then a final RMSNorm and the
output head (the embedding table when tied).  Every layer attends its whole
causal context.

A family module provides:

* ``model_config(conf)``: the program's ``ModelConfig``;
* ``engine_params(seed, conf)``: the served parameter tree, seeded random
  weights in the layout ``repro.models.model.Model`` reads;
* ``Reference(dims, quant=None)`` with ``.logits(seed, seqs)``: the plain
  float32 forward pass that decides ``correct``;
* the counts the per-layer readers divide by: ``total_params``,
  ``kv_bytes_per_token``, ``decode_token_flops``, ``prefill_flops`` and
  ``paged_attention_cost`` (the whole stack's, for one decode step).

``dims`` and ``conf`` are a configuration file's dict (Hugging Face key
names).
"""
from __future__ import annotations

import math
from typing import Iterable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights as W
from chipbench.reference import MIN_PAD, ROUNDERS


def _shape(dims: dict):
    d = dims["hidden_size"]
    h = dims["num_attention_heads"]
    hkv = dims["num_key_value_heads"]
    hd = dims.get("head_dim") or d // h
    return d, h, hkv, hd, dims["intermediate_size"]


def model_config(conf: dict):
    """The program's ``ModelConfig`` for a Qwen2 configuration file."""
    from repro.configs import ModelConfig
    d, h, hkv, hd, dff = _shape(conf)
    return ModelConfig(
        name=conf["name"], family="dense",
        num_layers=conf["num_hidden_layers"], d_model=d, num_heads=h,
        num_kv_heads=hkv, d_ff=dff, vocab_size=conf["vocab_size"],
        head_dim=hd, rope_theta=float(conf["rope_theta"]),
        qkv_bias=bool(conf["attention_bias"]),
        rms_eps=float(conf["rms_norm_eps"]),
        tie_embeddings=bool(conf["tie_word_embeddings"]),
        source=conf["source"], dtype=conf["torch_dtype"])


# -- weights ------------------------------------------------------------------
# ``engine_params`` makes the whole tree in one jitted call; ``layer_weights``
# and ``table_weights`` make the same values one layer at a time, so the
# reference can rebuild them layer by layer after the program's state is
# freed.  The q/k/v biases are non-zero (``W.BIAS_STD``) so their path is
# checked.
#
# With tied embeddings the head is the embedding table, and the usual recipe
# (a small table, a final norm gain near 1) makes greedy outputs collapse:
# the layers' input-independent parts (biases, averaged values) swamp the
# small token embeddings, and every position predicts one token, so no
# precision error can flip a served token.  Tied tables are therefore +-1
# entries, which keep the token in the residual stream, and the final norm
# gain has zero sum and carries the logit scale: a token's logit for itself
# is then ``sum(gain * e * e) = sum(gain) = 0``, so it predicts neither
# itself nor a constant.

def layer_weights(key: jax.Array, dims: dict, dtype) -> dict:
    """One decoder layer, in the program's per-layer layout."""
    d, h, hkv, hd, dff = _shape(dims)
    k = jax.random.split(key, 12)
    return {
        "attn": {
            "ln": W._norm(k[0], d, dtype),
            "wq": W._normal(k[1], (d, h * hd), 1 / math.sqrt(d), dtype),
            "wk": W._normal(k[2], (d, hkv * hd), 1 / math.sqrt(d), dtype),
            "wv": W._normal(k[3], (d, hkv * hd), 1 / math.sqrt(d), dtype),
            "wo": W._normal(k[4], (h * hd, d), 1 / math.sqrt(h * hd), dtype),
            "bq": W._normal(k[5], (h * hd,), W.BIAS_STD, dtype),
            "bk": W._normal(k[6], (hkv * hd,), W.BIAS_STD, dtype),
            "bv": W._normal(k[7], (hkv * hd,), W.BIAS_STD, dtype),
        },
        "ffn_ln": W._norm(k[8], d, dtype),
        "ffn": {
            "w_gate": W._normal(k[9], (d, dff), 1 / math.sqrt(d), dtype),
            "w_up": W._normal(k[10], (d, dff), 1 / math.sqrt(d), dtype),
            "w_down": W._normal(k[11], (dff, d), 1 / math.sqrt(dff), dtype),
        },
    }


def table_weights(key: jax.Array, dims: dict, dtype) -> dict:
    """Embedding, final norm and (untied) output head."""
    d, v = dims["hidden_size"], dims["vocab_size"]
    k = jax.random.split(key, 3)
    head_std = W.LOGIT_STD / math.sqrt(d)
    out = {"final_ln": W._norm(k[1], d, dtype)}
    if dims["tie_word_embeddings"]:
        signs = jax.random.bernoulli(k[0], 0.5, (v, d))
        out["embed"] = jnp.where(signs, 1.0, -1.0).astype(dtype)
        g = jax.random.normal(k[1], (d,), jnp.float32)
        out["final_ln"] = ((g - g.mean()) * head_std).astype(dtype)
    else:
        out["embed"] = W._normal(k[0], (v, d), 1.0, dtype)
        out["lm_head"] = W._normal(k[2], (d, v), head_std, dtype)
    return out


def layer_key(base: jax.Array, i: int) -> jax.Array:
    return jax.random.fold_in(base, i + 1)


def engine_params(seed: int, dims: dict) -> dict:
    """The served parameter tree, made on the default device in one jitted
    call: the tables, and every layer stacked in ``block.pos0``."""
    dtype = jnp.dtype(dims["torch_dtype"])
    n = dims["num_hidden_layers"]

    def make(base):
        keys = jnp.stack([layer_key(base, i) for i in range(n)])
        params = table_weights(jax.random.fold_in(base, 0), dims, dtype)
        params["prefix_layers"] = []
        params["block"] = {"pos0": jax.vmap(
            lambda kk: layer_weights(kk, dims, dtype))(keys)}
        return params

    return jax.jit(make)(W.seed_key(seed, W.WEIGHTS))


# -- reference ----------------------------------------------------------------

class Reference:
    """Qwen2 forward in float32 for one configuration (``dims``: the config
    file), the weights rebuilt from the seed one layer at a time; ``quant``
    (a precision of ``reference.ROUNDERS``) rounds every linear layer's
    weights and inputs, for the control."""

    def __init__(self, dims: dict, quant: Optional[str] = None):
        self.dims = dims
        self.quant = quant
        _, self.h, self.hkv, self.hd, _ = _shape(dims)
        self.eps = float(dims["rms_norm_eps"])
        self.theta = float(dims["rope_theta"])
        self._layer = jax.jit(self._layer_fn)
        self._embed = jax.jit(lambda table, toks: table[toks].astype(
            jnp.float32))
        self._head = jax.jit(self._head_fn)
        self._gen_layer = jax.jit(lambda key: jax.tree.map(
            lambda a: a.astype(jnp.float32),
            layer_weights(key, dims, jnp.dtype(dims["torch_dtype"]))))
        self._gen_tables = jax.jit(lambda key: jax.tree.map(
            lambda a: a.astype(jnp.float32),
            table_weights(key, dims, jnp.dtype(dims["torch_dtype"]))))

    # -- pieces -------------------------------------------------------------
    def _mm(self, x, w):
        """``x @ w`` for a linear layer; the control rounds both sides."""
        if self.quant is not None:
            rnd = ROUNDERS[self.quant]
            x, w = rnd(x, -1), rnd(w, 0)
        return x @ w

    def _rms(self, x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                 + self.eps) * w

    def _rope(self, x, pos):                       # x [T, heads, hd]
        half = self.hd // 2
        inv = 1.0 / (self.theta ** (jnp.arange(half, dtype=jnp.float32)
                                    * 2.0 / self.hd))
        ang = pos[:, None].astype(jnp.float32) * inv[None, :]
        cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    def _layer_fn(self, x, p):                     # x [T, D] float32
        t = x.shape[0]
        a = p["attn"]
        h = self._rms(x, a["ln"])
        q = (self._mm(h, a["wq"]) + a["bq"]).reshape(t, self.h, self.hd)
        k = (self._mm(h, a["wk"]) + a["bk"]).reshape(t, self.hkv, self.hd)
        v = (self._mm(h, a["wv"]) + a["bv"]).reshape(t, self.hkv, self.hd)
        pos = jnp.arange(t)
        q, k = self._rope(q, pos), self._rope(k, pos)
        g = self.h // self.hkv
        q = q.reshape(t, self.hkv, g, self.hd)
        s = jnp.einsum("qhgd,khd->hgqk", q, k) / math.sqrt(self.hd)
        causal = pos[:, None] >= pos[None, :]
        s = jnp.where(causal, s, -jnp.inf)
        o = jnp.einsum("hgqk,khd->qhgd", jax.nn.softmax(s, axis=-1), v)
        x = x + self._mm(o.reshape(t, self.h * self.hd), a["wo"])
        h = self._rms(x, p["ffn_ln"])
        f = p["ffn"]
        u = jax.nn.silu(self._mm(h, f["w_gate"])) * self._mm(h, f["w_up"])
        return x + self._mm(u, f["w_down"])

    def _head_fn(self, x, rows, final_ln, head):
        return self._mm(self._rms(x[rows], final_ln), head)

    # -- the pass -----------------------------------------------------------
    def logits(self, seed: int, seqs: Sequence[Tuple[List[int], int]]
               ) -> List[jax.Array]:
        """For each (tokens, n): the logits [n, vocab] that predict the last
        ``n`` tokens of ``tokens``, from one causal pass over ``tokens``."""
        base = W.seed_key(seed, W.WEIGHTS)
        with jax.default_matmul_precision("highest"):
            tables = self._gen_tables(jax.random.fold_in(base, 0))
            xs = []
            for toks, _ in seqs:
                padded = np.zeros(max(MIN_PAD, 1 << (len(toks) - 1)
                                      .bit_length()), np.int32)
                padded[:len(toks)] = toks
                xs.append(self._embed(tables["embed"], jnp.asarray(padded)))
            for i in range(self.dims["num_hidden_layers"]):
                p = self._gen_layer(layer_key(base, i))
                xs = [self._layer(x, p) for x in xs]
                del p
            head = (tables["embed"].T if self.dims["tie_word_embeddings"]
                    else tables["lm_head"])
            out = []
            for x, (toks, n) in zip(xs, seqs):
                rows = jnp.arange(len(toks) - 1 - n, len(toks) - 1)
                out.append(self._head(x, rows, tables["final_ln"], head))
            return out


# -- counts (by the rule in ``flops.py``) ---------------------------------------

def layer_matmul_params(dims: dict) -> int:
    """Weight-matrix entries of one layer (q, k, v, o, gate, up, down)."""
    d, h, hkv, hd, dff = _shape(dims)
    return d * h * hd + 2 * d * hkv * hd + h * hd * d + 3 * d * dff


def layer_params(dims: dict) -> int:
    """All parameters of one layer: matrices, q/k/v biases, two norms."""
    d, h, hkv, hd, _ = _shape(dims)
    return layer_matmul_params(dims) + h * hd + 2 * hkv * hd + 2 * d


def embedding_params(dims: dict) -> int:
    return dims["vocab_size"] * dims["hidden_size"]


def total_params(dims: dict) -> int:
    """Embedding, layers, final norm, and the output head unless tied."""
    tables = 1 if dims["tie_word_embeddings"] else 2
    return (tables * embedding_params(dims)
            + dims["num_hidden_layers"] * layer_params(dims)
            + dims["hidden_size"])


def kv_bytes_per_token(dims: dict, bytes_per_el: int = 2) -> int:
    _, _, hkv, hd, _ = _shape(dims)
    return dims["num_hidden_layers"] * 2 * hkv * hd * bytes_per_el


def attention_flops(dims: dict, context: int) -> int:
    """One query token over ``context`` keys, one layer: QK^T and PV."""
    _, h, _, hd, _ = _shape(dims)
    return 4 * context * h * hd


def decode_token_flops(dims: dict, context: int) -> int:
    """One decoded token attending ``context`` positions (itself included):
    every layer's matrices, its attention, and the output head."""
    n_layers = dims["num_hidden_layers"]
    return (2 * n_layers * layer_matmul_params(dims)
            + n_layers * attention_flops(dims, context)
            + 2 * embedding_params(dims))


def prefill_flops(dims: dict, offset: int, tokens: int) -> int:
    """``tokens`` prompt tokens at positions ``offset..offset+tokens-1``,
    causal over everything before them, and the head at the last one."""
    n_layers = dims["num_hidden_layers"]
    # sum over queries of their context lengths (p + 1 for position p)
    ctx_sum = tokens * offset + tokens * (tokens + 1) // 2
    _, h, _, hd, _ = _shape(dims)
    return (2 * tokens * n_layers * layer_matmul_params(dims)
            + n_layers * 4 * ctx_sum * h * hd
            + 2 * embedding_params(dims))


def paged_attention_cost(dims: dict, contexts: Iterable[int],
                         bytes_per_el: int = 2):
    """(operations, bytes) of one decode step's ``paged_attention`` calls,
    one per layer: one query token per live slot over that slot's live
    context.  Bytes are the live K and V cells read plus each slot's query
    and output.  Every Qwen2 layer attends the whole context."""
    _, h, hkv, hd, _ = _shape(dims)
    contexts = list(contexts)
    ctx = sum(contexts)
    n_layers = dims["num_hidden_layers"]
    ops = 4 * ctx * h * hd
    nbytes = (2 * ctx * hkv * hd * bytes_per_el
              + 2 * len(contexts) * h * hd * bytes_per_el)
    return n_layers * ops, n_layers * nbytes

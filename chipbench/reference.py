"""Plain float32 Qwen2 forward pass: the reference that decides ``correct``.

Written from the published description (arXiv:2407.10671; the Hugging Face
``Qwen2`` modelling code): pre-norm decoder layers of RMSNorm, grouped-query
self-attention with q/k/v biases and rotary embedding (rotate-half form,
base ``rope_theta``), SwiGLU MLP, final RMSNorm and the output head (the
embedding table when tied).  It imports nothing of the program and takes
nothing the program made: the weights come again from the seed, one layer
at a time (``weights.layer_weights``), so the whole model never has to sit
on the device in float32.  Matrix products run at ``highest`` precision.

``quant`` makes the control: the same pass with every linear layer's
weights (per output channel) and inputs (per token) rounded to float8 e4m3,
the precision below the served bfloat16.  The control is put
in the program's place: ``greedy_tokens`` and ``nucleus_tokens`` give what
it would serve, and the same comparison judges it.
"""
from __future__ import annotations

import math
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights as W

MIN_PAD = 256      # sequences pad to a power of two from here: few shapes


def _round_fp8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


ROUNDERS = {"fp8": _round_fp8}


class Reference:
    """Qwen2 forward for one configuration (``dims``: the config file)."""

    def __init__(self, dims: dict, quant: Optional[str] = None):
        self.dims = dims
        self.quant = quant
        d = dims["hidden_size"]
        self.h = dims["num_attention_heads"]
        self.hkv = dims["num_key_value_heads"]
        self.hd = dims.get("head_dim") or d // self.h
        self.eps = float(dims["rms_norm_eps"])
        self.theta = float(dims["rope_theta"])
        self._layer = jax.jit(self._layer_fn)
        self._embed = jax.jit(lambda table, toks: table[toks].astype(
            jnp.float32))
        self._head = jax.jit(self._head_fn)
        self._gen_layer = jax.jit(lambda key: jax.tree.map(
            lambda a: a.astype(jnp.float32),
            W.layer_weights(key, dims, jnp.dtype(dims["torch_dtype"]))))
        self._gen_tables = jax.jit(lambda key: jax.tree.map(
            lambda a: a.astype(jnp.float32),
            W.table_weights(key, dims, jnp.dtype(dims["torch_dtype"]))))

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
                p = self._gen_layer(W.layer_key(base, i))
                xs = [self._layer(x, p) for x in xs]
                del p
            head = (tables["embed"].T if self.dims["tie_word_embeddings"]
                    else tables["lm_head"])
            out = []
            for x, (toks, n) in zip(xs, seqs):
                rows = jnp.arange(len(toks) - 1 - n, len(toks) - 1)
                out.append(self._head(x, rows, tables["final_ln"], head))
            return out


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

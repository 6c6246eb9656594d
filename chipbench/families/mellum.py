"""JetBrains Mellum 2 (``model_type`` ``mellum``; the published
``config.json`` of Mellum2-12B-A2.5B): what the harness needs of this
architecture, found by ``cell.family`` for a configuration whose
``architectures[0]`` is ``MellumForCausalLM``.

The architecture: pre-norm decoder layers of RMSNorm, grouped-query
self-attention without biases and with rotary embedding (rotate-half form,
base ``rope_parameters[...]["rope_theta"]``), RMSNorm and a sparse expert
MLP; then a final RMSNorm and an untied output head.

* Attention kinds by ``layer_types``: a ``sliding_attention`` layer lets a
  query at position p see keys at positions greater than p - ``sliding_window``
  (the Hugging Face sliding-window mask), with the default rotation; a
  ``full_attention`` layer sees the whole causal context, with YaRN
  (``rope_parameters["full_attention"]``: frequencies by the Hugging Face
  ``_compute_yarn_parameters``, cos and sin times ``attention_factor``).
* Every MLP is sparse (``mlp_layer_types``): a router scores all
  ``published["num_experts"]`` experts, softmax, the top
  ``num_experts_per_tok``, their gates divided by their sum
  (``norm_topk_prob``); each picked expert is a SwiGLU MLP of width
  ``moe_intermediate_size``; no shared expert.

The configuration holds one chip's share of the experts (``held_experts``,
``num_experts`` of them): the program and the reference both compute the
part of each expert layer's output those experts give, and what the experts
held on other chips would add is left out of both.

The module gives what ``families/qwen2.py`` gives (its docstring says what
each is), and ``expert_gmm_cost`` for the grouped expert kernel.  The
reference imports nothing of the program.
"""
from __future__ import annotations

import math
from typing import Iterable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights as W
from chipbench.reference import MIN_PAD, ROUNDERS

# The router's logits have about this standard deviation over the experts
# (router entries N(0, (ROUTER_LOGIT_STD / sqrt(d))^2) on a unit-RMS input):
# the top 8 of 64 then hold most of the mass, as a trained router's do, and
# the gate of a token's 8th expert is a few percent of its first.
ROUTER_LOGIT_STD = 2.0


def _shape(dims: dict):
    d = dims["hidden_size"]
    h = dims["num_attention_heads"]
    return d, h, dims["num_key_value_heads"], dims["head_dim"]


def _experts(dims: dict):
    """(experts the router scores, the held range [lo, hi), top k, width)."""
    routed = dims.get("published", {}).get("num_experts", dims["num_experts"])
    lo, hi = dims.get("held_experts", (0, dims["num_experts"]))
    assert hi - lo == dims["num_experts"], (lo, hi, dims["num_experts"])
    return routed, (lo, hi), dims["num_experts_per_tok"], \
        dims["moe_intermediate_size"]


def _windows(dims: dict) -> List[int]:
    """Each layer's attention window, 0 for a full-attention layer."""
    return [dims["sliding_window"] if t == "sliding_attention" else 0
            for t in dims["layer_types"][:dims["num_hidden_layers"]]]


def model_config(conf: dict):
    """The program's ``ModelConfig`` for a Mellum 2 configuration file."""
    from repro.configs import ModelConfig, MoEConfig, RopeScaling
    d, h, hkv, hd = _shape(conf)
    routed, held, k, dff = _experts(conf)
    assert set(conf["mlp_layer_types"]) == {"sparse"}
    assert not conf["attention_bias"]
    rope = conf["rope_parameters"]
    full, sliding = rope["full_attention"], rope["sliding_attention"]
    assert sliding["rope_type"] == "default" and full["rope_type"] == "yarn"
    assert sliding["rope_theta"] == full["rope_theta"]
    return ModelConfig(
        name=conf["name"], family="moe",
        num_layers=conf["num_hidden_layers"], d_model=d, num_heads=h,
        num_kv_heads=hkv, d_ff=conf["intermediate_size"],
        vocab_size=conf["vocab_size"], head_dim=hd,
        rope_theta=float(full["rope_theta"]),
        rms_eps=float(conf["rms_norm_eps"]),
        tie_embeddings=bool(conf["tie_word_embeddings"]),
        layer_windows=tuple(_windows(conf)),
        rope_scaling=RopeScaling(
            factor=float(full["factor"]),
            original_max_position_embeddings=int(
                full["original_max_position_embeddings"]),
            beta_fast=float(full["beta_fast"]),
            beta_slow=float(full["beta_slow"]),
            attention_factor=float(full["attention_factor"])),
        moe=MoEConfig(num_experts=routed, experts_per_token=k,
                      expert_d_ff=dff, held=held),
        source=conf["source"], dtype=conf["torch_dtype"])


# -- weights ------------------------------------------------------------------
# Scales as ``families/qwen2.py``'s: matrices N(0, 1/fan_in), norm gains near
# 1, a N(0, 1) embedding table and a head giving logits of std about
# ``W.LOGIT_STD``; the router as ``ROUTER_LOGIT_STD`` says.  The router is
# stored in the served dtype, as the published checkpoint stores it.

def _expert(key: jax.Array, i, d: int, f: int, dtype):
    """Expert ``i``'s gate, up and down matrices: drawn by its own index, so
    a chip's share holds the same experts the uncut layer holds."""
    k = jax.random.split(jax.random.fold_in(key, i), 3)
    return (W._normal(k[0], (d, f), 1 / math.sqrt(d), dtype),
            W._normal(k[1], (d, f), 1 / math.sqrt(d), dtype),
            W._normal(k[2], (f, d), 1 / math.sqrt(f), dtype))


def layer_weights(key: jax.Array, dims: dict, dtype) -> dict:
    """One decoder layer, in the program's per-layer layout: its held
    experts stacked ``[held, ...]``."""
    d, h, hkv, hd = _shape(dims)
    routed, (lo, hi), _, f = _experts(dims)
    k = jax.random.split(key, 8)
    wg, wu, wd = jax.vmap(lambda i: _expert(k[7], i, d, f, dtype))(
        jnp.arange(lo, hi))
    return {
        "attn": {
            "ln": W._norm(k[0], d, dtype),
            "wq": W._normal(k[1], (d, h * hd), 1 / math.sqrt(d), dtype),
            "wk": W._normal(k[2], (d, hkv * hd), 1 / math.sqrt(d), dtype),
            "wv": W._normal(k[3], (d, hkv * hd), 1 / math.sqrt(d), dtype),
            "wo": W._normal(k[4], (h * hd, d), 1 / math.sqrt(h * hd), dtype),
        },
        "moe": {
            "ln": W._norm(k[5], d, dtype),
            "router": W._normal(k[6], (d, routed),
                                ROUTER_LOGIT_STD / math.sqrt(d), dtype),
            "we_gate": wg, "we_up": wu, "we_down": wd,
        },
    }


def table_weights(key: jax.Array, dims: dict, dtype) -> dict:
    """Embedding, final norm and the untied output head."""
    d, v = dims["hidden_size"], dims["vocab_size"]
    assert not dims["tie_word_embeddings"]
    k = jax.random.split(key, 3)
    return {"embed": W._normal(k[0], (v, d), 1.0, dtype),
            "final_ln": W._norm(k[1], d, dtype),
            "lm_head": W._normal(k[2], (d, v), W.LOGIT_STD / math.sqrt(d),
                                 dtype)}


def layer_key(base: jax.Array, i: int) -> jax.Array:
    return jax.random.fold_in(base, i + 1)


def engine_params(seed: int, dims: dict) -> dict:
    """The served parameter tree, made on the default device in one jitted
    call, in the program's layout: the tables, the layers before the
    repeated pattern one by one, then the pattern's layer ``j`` of repeat
    ``g`` at index ``g`` of ``block.pos{j}``."""
    from repro.models.model import find_pattern
    prefix, pattern, repeats = find_pattern(model_config(dims).layer_kinds())
    dtype = jnp.dtype(dims["torch_dtype"])
    n0, period = len(prefix), len(pattern)

    def make(base):
        params = table_weights(jax.random.fold_in(base, 0), dims, dtype)
        params["prefix_layers"] = [
            layer_weights(layer_key(base, i), dims, dtype) for i in range(n0)]
        params["block"] = {}
        for j in range(period):
            keys = jnp.stack([layer_key(base, n0 + g * period + j)
                              for g in range(repeats)])
            params["block"][f"pos{j}"] = jax.vmap(
                lambda kk: layer_weights(kk, dims, dtype))(keys)
        return params

    return jax.jit(make)(W.seed_key(seed, W.WEIGHTS))


# -- reference ----------------------------------------------------------------

def _yarn_inv_freq(hd: int, theta: float, p: dict) -> np.ndarray:
    """YaRN frequencies (arXiv:2309.00071 as Hugging Face computes them):
    each of the hd/2 dimensions keeps 1 / theta^(2i/hd) if it turns more
    than ``beta_fast`` times over the original context, takes that divided
    by ``factor`` if it turns fewer than ``beta_slow`` times, and between
    the two (dimensions floor(low)..ceil(high)) a linear blend."""
    base = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    n_orig = p["original_max_position_embeddings"]

    def dim_turning(rotations):     # the dimension that turns this often
        return hd * math.log(n_orig / (rotations * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(dim_turning(p["beta_fast"])), 0)
    high = min(math.ceil(dim_turning(p["beta_slow"])), hd - 1)
    blend = np.clip((np.arange(hd // 2) - low) / max(high - low, 1e-3), 0, 1)
    return base * (1 - blend) + base / p["factor"] * blend


class Reference:
    """Mellum 2 forward in float32 for one configuration (``dims``: the
    config file), the weights rebuilt from the seed one layer at a time;
    ``quant`` (a precision of ``reference.ROUNDERS``) rounds every linear
    layer's weights and inputs, the router's among them, for the control."""

    def __init__(self, dims: dict, quant: Optional[str] = None):
        self.dims = dims
        self.quant = quant
        _, self.h, self.hkv, self.hd = _shape(dims)
        self.routed, (self.lo, self.hi), self.k, _ = _experts(dims)
        self.eps = float(dims["rms_norm_eps"])
        self.windows = _windows(dims)
        rope = dims["rope_parameters"]
        full = rope["full_attention"]
        self.theta = float(rope["sliding_attention"]["rope_theta"])
        self.yarn = (_yarn_inv_freq(self.hd, float(full["rope_theta"]), full),
                     float(full["attention_factor"]))
        self._layer = jax.jit(self._layer_fn, static_argnames=("window",))
        self._embed = jax.jit(lambda table, toks: table[toks].astype(
            jnp.float32))
        self._head = jax.jit(self._head_fn)
        dtype = jnp.dtype(dims["torch_dtype"])
        self._gen_layer = jax.jit(lambda key: jax.tree.map(
            lambda a: a.astype(jnp.float32), layer_weights(key, dims, dtype)))
        self._gen_tables = jax.jit(lambda key: jax.tree.map(
            lambda a: a.astype(jnp.float32), table_weights(key, dims, dtype)))

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

    def _rope(self, x, pos, inv, mag):             # x [T, heads, hd]
        half = self.hd // 2
        ang = pos[:, None].astype(jnp.float32) * jnp.asarray(
            inv, jnp.float32)[None, :]
        cos, sin = jnp.cos(ang)[:, None] * mag, jnp.sin(ang)[:, None] * mag
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    def _experts_fn(self, h, m):
        """The held experts' part of the expert layer for every token: each
        held expert's MLP on every token, times the token's gate for it where
        the expert is among the token's own top k (routed on these float32
        scores), else zero."""
        probs = jax.nn.softmax(self._mm(h, m["router"]), axis=-1)   # [T, E]
        top, picked = jax.lax.top_k(probs, self.k)
        gates = top / top.sum(-1, keepdims=True)
        out = jnp.zeros_like(h)
        for e in range(self.lo, self.hi):
            gate = jnp.sum(jnp.where(picked == e, gates, 0.0), axis=-1)
            i = e - self.lo
            u = (jax.nn.silu(self._mm(h, m["we_gate"][i]))
                 * self._mm(h, m["we_up"][i]))
            out = out + gate[:, None] * self._mm(u, m["we_down"][i])
        return out

    def _layer_fn(self, x, p, *, window):          # x [T, D] float32
        t = x.shape[0]
        a = p["attn"]
        h = self._rms(x, a["ln"])
        q = self._mm(h, a["wq"]).reshape(t, self.h, self.hd)
        k = self._mm(h, a["wk"]).reshape(t, self.hkv, self.hd)
        v = self._mm(h, a["wv"]).reshape(t, self.hkv, self.hd)
        pos = jnp.arange(t)
        if window:
            inv = 1.0 / self.theta ** (np.arange(0, self.hd, 2) / self.hd)
            q, k = self._rope(q, pos, inv, 1.0), self._rope(k, pos, inv, 1.0)
        else:
            q, k = self._rope(q, pos, *self.yarn), self._rope(k, pos, *self.yarn)
        g = self.h // self.hkv
        q = q.reshape(t, self.hkv, g, self.hd)
        s = jnp.einsum("qhgd,khd->hgqk", q, k) / math.sqrt(self.hd)
        seen = pos[:, None] >= pos[None, :]
        if window:
            seen &= pos[None, :] > pos[:, None] - window
        s = jnp.where(seen, s, -jnp.inf)
        o = jnp.einsum("hgqk,khd->qhgd", jax.nn.softmax(s, axis=-1), v)
        x = x + self._mm(o.reshape(t, self.h * self.hd), a["wo"])
        return x + self._experts_fn(self._rms(x, p["moe"]["ln"]), p["moe"])

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
            for i, window in enumerate(self.windows):
                p = self._gen_layer(layer_key(base, i))
                xs = [self._layer(x, p, window=window) for x in xs]
                del p
            out = []
            for x, (toks, n) in zip(xs, seqs):
                rows = jnp.arange(len(toks) - 1 - n, len(toks) - 1)
                out.append(self._head(x, rows, tables["final_ln"],
                                      tables["lm_head"]))
            return out


# -- counts (by the rule in ``flops.py``) ---------------------------------------

def attn_matmul_params(dims: dict) -> int:
    """q, k, v and o of one layer."""
    d, h, hkv, hd = _shape(dims)
    return 2 * d * h * hd + 2 * d * hkv * hd


def expert_params(dims: dict) -> int:
    """One expert's gate, up and down matrices."""
    return 3 * dims["hidden_size"] * dims["moe_intermediate_size"]


def layer_params(dims: dict) -> int:
    """All parameters of one layer: attention, router, the held experts and
    two norms."""
    routed = _experts(dims)[0]
    d = dims["hidden_size"]
    return (attn_matmul_params(dims) + d * routed
            + dims["num_experts"] * expert_params(dims) + 2 * d)


def embedding_params(dims: dict) -> int:
    return dims["vocab_size"] * dims["hidden_size"]


def total_params(dims: dict) -> int:
    """Embedding, layers, final norm and the untied output head."""
    return (2 * embedding_params(dims)
            + dims["num_hidden_layers"] * layer_params(dims)
            + dims["hidden_size"])


def kv_bytes_per_token(dims: dict, bytes_per_el: int = 2) -> int:
    """Every layer keeps K and V for its whole context (the windowed layers'
    pages are not recycled)."""
    _, _, hkv, hd = _shape(dims)
    return dims["num_hidden_layers"] * 2 * hkv * hd * bytes_per_el


def expert_evals_per_token(dims: dict) -> float:
    """Expected held experts a token meets in one layer: k x held / E."""
    routed, _, k, _ = _experts(dims)
    return k * dims["num_experts"] / routed


def _layer_token_flops(dims: dict) -> float:
    """One token through one layer's matrices: attention, router and the
    expected held-expert evaluations."""
    d = dims["hidden_size"]
    return 2 * (attn_matmul_params(dims) + d * _experts(dims)[0]
                + expert_evals_per_token(dims) * expert_params(dims))


def decode_token_flops(dims: dict, context: int) -> float:
    """One decoded token attending ``context`` positions (itself included),
    a windowed layer at most its window: every layer's matrices, the held
    experts it meets, its attention, and the output head."""
    _, h, _, hd = _shape(dims)
    attn = sum(4 * (min(context, w) if w else context) * h * hd
               for w in _windows(dims))
    return (dims["num_hidden_layers"] * _layer_token_flops(dims) + attn
            + 2 * embedding_params(dims))


def _ctx_sum(offset: int, tokens: int, window: int) -> int:
    """Sum over positions offset..offset+tokens-1 of the keys each sees:
    p + 1, or at most ``window``."""
    def upto(n):            # positions 0..n-1
        if not window or n <= window:
            return n * (n + 1) // 2
        return window * (window + 1) // 2 + (n - window) * window
    return upto(offset + tokens) - upto(offset)


def prefill_flops(dims: dict, offset: int, tokens: int) -> float:
    """``tokens`` prompt tokens at positions ``offset..offset+tokens-1``,
    causal over everything before them (a windowed layer over its window),
    and the head at the last one."""
    _, h, _, hd = _shape(dims)
    attn = sum(4 * _ctx_sum(offset, tokens, w) * h * hd
               for w in _windows(dims))
    return (tokens * dims["num_hidden_layers"] * _layer_token_flops(dims)
            + attn + 2 * embedding_params(dims))


def paged_attention_cost(dims: dict, contexts: Iterable[int],
                         bytes_per_el: int = 2):
    """(operations, bytes) of one decode step's ``paged_attention`` calls,
    one per layer: one query token per live slot over that slot's live
    context, a windowed layer's capped at its window.  Bytes are the K and V
    cells read plus each slot's query and output."""
    _, h, hkv, hd = _shape(dims)
    contexts = list(contexts)
    ops = nbytes = 0
    for w in _windows(dims):
        ctx = sum(min(c, w) if w else c for c in contexts)
        ops += 4 * ctx * h * hd
        nbytes += (2 * ctx * hkv * hd * bytes_per_el
                   + 2 * len(contexts) * h * hd * bytes_per_el)
    return ops, nbytes


def expert_gmm_cost(dims: dict, rows: float, experts: float,
                    bytes_per_el: int = 2):
    """(operations, bytes) of one ``expert_gmm`` call, one expert layer of
    one decode step: ``rows`` token-expert rows through gate, up and down;
    the weights of the ``experts`` held experts that computed any row, each
    read once (an expert with no row is never visited), and the rows in and
    out."""
    ops = 2 * rows * expert_params(dims)
    nbytes = (experts * expert_params(dims)
              + 2 * rows * dims["hidden_size"]) * bytes_per_el
    return ops, nbytes

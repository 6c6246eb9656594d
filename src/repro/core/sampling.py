"""Token sampling: per-slot temperature / top-k / top-p / min-p, device-resident.

Continuous batching serves heterogeneous requests, so every decode slot carries
its *own* sampling parameters and its own PRNG key stream: the engine's
``decode_block`` folds :func:`masked_sample_inner` straight into the
``lax.scan`` decode loop, so masking, the per-step key derivation, and the
categorical draw all happen on-device with no host round-trip between tokens
and no per-request recompilation (every mask is computed at the fixed vocab
width).

Semantics (shared by the compiled kernel and the host reference):

* ``temperature == 0`` is greedy (argmax) — bit-identical to the pre-per-slot
  engine-level path, and independent of every other parameter and of the RNG.
* ``top_k`` / ``top_p`` / ``min_p`` each keep a *prefix* of the
  descending-sorted, temperature-scaled distribution: the ``top_k`` largest
  logits; the smallest set with cumulative probability ``>= top_p``, where —
  following the HF/vLLM composition convention (and the previous engine-level
  masks) — the cumulative mass is renormalized to the surviving top-k prefix
  when ``top_k`` is active; and tokens with probability ``>= min_p *
  max_prob`` (on the full distribution).  The slot's keep-set is the shortest
  of the three prefixes, realised as one value threshold (ties at the
  threshold are kept, matching the previous engine-level masks).  ``top_k=0``,
  ``top_p=1`` and ``min_p=0`` are exact no-ops (the masked logits are bitwise
  the scaled logits).
* RNG is **stateless per token**: the key for the token at absolute position
  ``p`` is ``fold_in(base_key, p)`` (:func:`fold_step_keys`), where
  ``base_key`` derives from the request's optional ``seed``
  (:func:`request_base_key`).  No split chain means a slot's stream depends
  only on its own base key and positions — neighbours in the batch, the block
  size K, preemption/resume, and the logprobs decode-block variant can never
  perturb it, and a seeded request replays identically across runs.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing


def request_base_key(seed: int) -> np.ndarray:
    """Base PRNG key for a seeded request: depends on the seed alone (never on
    engine seed, arrival order, or slot), so seeded replay holds across runs.

    The seed is consumed as two explicit 32-bit halves: ``PRNGKey`` alone
    would silently truncate seeds >= 2**32 to their low word (aliasing
    high-bit-distinct seeds, and doing so differently under
    ``jax_enable_x64``), so the high half is folded in separately — every
    seed in [0, 2**63) maps to a distinct key, identically in every process
    configuration."""
    low, high = seed & 0xFFFFFFFF, seed >> 32
    key = jax.random.PRNGKey(low)
    if high:
        key = jax.random.fold_in(key, high)
    return np.asarray(key)


def fold_step_keys(base_keys: jax.Array, positions: jax.Array) -> jax.Array:
    """Per-slot step keys: fold each slot's token position into its base key.

    Stateless derivation (``key_p = fold_in(base, p)``) is what makes seeded
    replay survive preemption/resume: restoring ``positions`` restores the
    exact key stream, with no split chain to replay."""
    return jax.vmap(jax.random.fold_in)(base_keys, positions)


def mask_scaled_logits(
    scaled: jax.Array,  # [B, V] f32 — temperature-scaled logits
    top_p: jax.Array,  # [B] f32 (1 = off)
    top_k: jax.Array,  # [B] int32 (0 = off)
    min_p: jax.Array,  # [B] f32 (0 = off)
) -> jax.Array:
    """Apply the per-slot prefix-threshold masks to temperature-scaled logits.

    The single source of the top-k/top-p/min-p keep-set semantics, shared by
    the sampling kernel below and the speculative-decoding verification path
    (core/spec_decode.py), which needs the masked *distribution* a stochastic
    slot draws from — not just one sample — for its rejection-sampling
    correction."""
    vocab = scaled.shape[-1]
    sorted_desc = jnp.sort(scaled, axis=-1)[:, ::-1]
    probs = jax.nn.softmax(sorted_desc, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    # each filter keeps a prefix of the sorted order; the keep-set is the
    # shortest prefix, applied as one value threshold (ties kept)
    n_k = jnp.where(top_k > 0, jnp.minimum(top_k, vocab), vocab)
    # top_p composes with top_k the HF/vLLM way: cumulative mass is
    # renormalized to the surviving top-k prefix (denominator 1 when top_k
    # is off, so plain nucleus sampling is untouched)
    ranks = jnp.arange(vocab)[None, :]
    k_mass = jnp.take_along_axis(cum, (n_k - 1)[:, None], axis=-1)
    denom = jnp.where((n_k < vocab)[:, None], k_mass, 1.0)
    in_k = ranks < n_k[:, None]
    n_p = jnp.where(
        top_p < 1.0,
        jnp.sum((cum / denom < top_p[:, None]) & in_k, axis=-1) + 1,
        vocab,
    )
    n_m = jnp.where(
        min_p > 0.0,
        jnp.sum(probs >= min_p[:, None] * probs[:, :1], axis=-1),
        vocab,
    )
    n_keep = jnp.clip(jnp.minimum(jnp.minimum(n_k, n_p), n_m), 1, vocab)
    cutoff = jnp.take_along_axis(sorted_desc, (n_keep - 1)[:, None], axis=-1)
    return jnp.where(scaled < cutoff, -jnp.inf, scaled)


def masked_probs(
    logits: jax.Array,  # [B, V] f32
    temperatures: jax.Array,  # [B] f32 (0 = greedy)
    top_p: jax.Array,  # [B] f32 (1 = off)
    top_k: jax.Array,  # [B] int32 (0 = off)
    min_p: jax.Array,  # [B] f32 (0 = off)
) -> jax.Array:
    """Per-slot token distribution under the masked sampler: the probability
    rows the stochastic path of :func:`masked_sample_inner` draws from
    (softmax of the masked scaled logits); greedy slots get the argmax point
    mass.  Used by the speculative-decoding verifier (target distribution
    ``p`` and draft distribution ``q`` of the rejection-sampling test)."""
    logits = logits.astype(jnp.float32)
    greedy = jax.nn.one_hot(jnp.argmax(logits, axis=-1), logits.shape[-1], dtype=jnp.float32)
    temps = jnp.maximum(temperatures, 1e-6)[:, None]
    masked = mask_scaled_logits(logits / temps, top_p, top_k, min_p)
    return jnp.where((temperatures > 0)[:, None], jax.nn.softmax(masked, axis=-1), greedy)


def masked_sample_inner(
    logits: jax.Array,  # [B, V] f32
    base_keys: jax.Array,  # [B, 2] uint32 — per-slot base keys
    positions: jax.Array,  # [B] int32 — position of the token being sampled
    temperatures: jax.Array,  # [B] f32 (0 = greedy)
    top_p: jax.Array,  # [B] f32 (1 = off)
    top_k: jax.Array,  # [B] int32 (0 = off)
    min_p: jax.Array,  # [B] f32 (0 = off)
) -> jax.Array:
    """Sample one token per slot with per-slot masked top-k/top-p/min-p.

    Shape-stable: one sort + cumulative-mass pass at the fixed vocab width
    covers every slot's parameters, so heterogeneous batches never recompile.
    The all-greedy case (every ``temperature == 0`` — the common mix, and the
    benchmark workload) skips everything stochastic — key folding, sort,
    softmax, categorical — via ``lax.cond``, keeping the block-decode hot
    loop at argmax cost (the pre-per-slot path paid an unconditional
    ``split`` per step; this pays nothing); a second inner ``cond`` lets
    plain temperature sampling (all mask knobs off) skip the sort pipeline
    too."""
    with tracing.scope("sampler"):
        logits = logits.astype(jnp.float32)
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

        def stochastic(_):
            keys = fold_step_keys(base_keys, positions)
            temps = jnp.maximum(temperatures, 1e-6)[:, None]
            scaled = logits / temps

            def masked(_):
                return mask_scaled_logits(scaled, top_p, top_k, min_p)

            # second fast path: plain temperature sampling (every mask knob off)
            # skips the O(B·V log V) sort pipeline and draws straight from the
            # scaled logits — bit-identical to the masked path, whose no-op
            # masks leave `scaled` bitwise unchanged
            any_mask = jnp.any((top_k > 0) | (top_p < 1.0) | (min_p > 0.0))
            target = jax.lax.cond(any_mask, masked, lambda _: scaled, operand=None)
            sampled = jax.vmap(jax.random.categorical)(keys, target).astype(jnp.int32)
            return jnp.where(temperatures > 0, sampled, greedy)

        return jax.lax.cond(jnp.any(temperatures > 0), stochastic, lambda _: greedy, operand=None)


masked_sample = jax.jit(masked_sample_inner)


def sample_reference(
    logits: np.ndarray,
    key: np.ndarray,
    temperature: float,
    top_p: float = 1.0,
    top_k: int = 0,
    min_p: float = 0.0,
) -> int:
    """Host reference sampler for one slot: independent numpy implementation
    of the prefix-threshold mask semantics above, plus the same categorical
    draw.  The hypothesis property in tests/test_decode_block.py holds the
    compiled batched kernel to this, token for token."""
    row = np.asarray(logits, np.float32)
    if temperature <= 0:
        return int(np.argmax(row))
    scaled = row / np.float32(max(temperature, 1e-6))
    order = np.sort(scaled)[::-1]
    shifted = np.exp(order - order[0])
    probs = shifted / shifted.sum()
    cum = np.cumsum(probs)
    vocab = row.size
    n_k = min(int(top_k), vocab) if top_k > 0 else vocab
    n_keep = n_k
    if top_p < 1.0:
        denom = cum[n_k - 1] if n_k < vocab else np.float32(1.0)
        n_keep = min(n_keep, int(np.sum(cum[:n_k] / denom < np.float32(top_p))) + 1)
    if min_p > 0.0:
        n_keep = min(n_keep, int(np.sum(probs >= np.float32(min_p) * probs[0])))
    n_keep = max(min(n_keep, vocab), 1)
    cutoff = order[n_keep - 1]
    masked = np.where(scaled < cutoff, -np.inf, scaled)
    return int(jax.random.categorical(jnp.asarray(key), jnp.asarray(masked)))


class SamplingParamError(ValueError):
    """Out-of-range sampler parameter; ``param`` names the offender so the
    OpenAI codec can map it into the structured error envelope."""

    def __init__(self, param: str, message: str):
        super().__init__(message)
        self.param = param


def validate_sampling_params(
    top_p: Optional[float], top_k: Optional[int], min_p: Optional[float], seed: Optional[int]
) -> None:
    """Range checks — the single source of the bounds, shared by the engine
    (``add_request``, hence ``EngineClient.submit`` raising ``ValueError``)
    and the OpenAI codec (mapping :class:`SamplingParamError` to the 400
    envelope).  ``None`` means "fall back to the engine default" and is
    always accepted."""
    if top_p is not None and not 0.0 < top_p <= 1.0:
        raise SamplingParamError("top_p", f"top_p must be in (0, 1], got {top_p}")
    if top_k is not None and top_k < 0:
        raise SamplingParamError("top_k", f"top_k must be >= 0 (0 = off), got {top_k}")
    if min_p is not None and not 0.0 <= min_p < 1.0:
        raise SamplingParamError("min_p", f"min_p must be in [0, 1), got {min_p}")
    if seed is not None and not 0 <= seed < 2**63:
        raise SamplingParamError("seed", f"seed must be an integer in [0, 2**63), got {seed}")

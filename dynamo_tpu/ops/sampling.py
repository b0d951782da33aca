"""Batched on-device token sampling: greedy / temperature / top-k / top-p /
frequency+presence penalties / per-request seeds / logprobs.

All requests in a decode batch sample in one fused op with per-request
parameters as arrays — no host round-trip per request.  temperature == 0
means greedy regardless of the other knobs.

Reference semantics: lib/llm/src/protocols/common.rs SamplingOptions
(temperature/top_p/top_k/frequency_penalty/presence_penalty/seed) — the
reference hands these to vLLM's sampler; this is the TPU-native sampler.

Cost shape matters here: this runs inside every decode step, and a full-vocab
sort (bitonic on TPU) of [B, 128k] costs more than an entire memory-bound
decode layer.  So the filtered path uses ONE sort (top-k and top-p both read
the same descending-sorted copy), and runtime ``lax.cond`` branches skip the
sort / penalties / logprobs work entirely when no row needs them — HLO
conditionals execute only the taken branch on device.

Randomness: each row draws from ``fold_in(PRNGKey(seed), step)`` where
``step`` is the row's output-token index — a request's sampled tokens are
reproducible regardless of how it was batched or preempted.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30
# Top-k logprobs returned when logprobs are requested.  20 is the OpenAI
# API's documented top_logprobs maximum (the edge rejects anything larger),
# so no valid request is ever silently clamped (ADVICE r3).
TOPK_LOGPROBS = 20


class SampleOut(NamedTuple):
    tokens: jnp.ndarray  # [B] int32
    logprob: jnp.ndarray  # [B] f32 — raw log p(sampled token)
    top_ids: jnp.ndarray  # [B, TOPK_LOGPROBS] int32
    top_logprobs: jnp.ndarray  # [B, TOPK_LOGPROBS] f32
    # A small int32 array some model families send home with the sampled
    # tokens (models/family.py ``forward``); None for the others, and a None
    # leaf adds nothing to a jitted program.
    aux: object = None


class SamplingParams(NamedTuple):
    """Per-row sampling state for one device step (host-built).

    Trailing fields default to None so pre-tenancy constructors keep
    working; None leaves vanish from the jit treedef, so engines that never
    use grammar masks / LoRA compile the exact same programs as before.
    """

    seeds: object  # [B] uint32
    steps: object  # [B] int32 — output-token index (rng stream position)
    temperature: object  # [B] f32
    top_k: object  # [B] int32
    top_p: object  # [B] f32
    freq_penalty: object  # [B] f32
    pres_penalty: object  # [B] f32
    counts: object  # [B, V] int16 output-token histogram
    need_logprobs: object  # [] bool
    # Grammar-constrained decoding (llm/tenancy/grammar.py): packed
    # admissible-token bitmask per row ([B, ceil(V/32)] uint32; bit i of
    # word i//32 = token i admissible) + an any-rows-masked scalar that
    # cond-skips the unpack entirely on unconstrained steps.
    mask_words: object = None  # [B, W] uint32 | None
    any_mask: object = None  # [] bool | None
    # Batched multi-LoRA (llm/tenancy/lora.py): per-row resident adapter
    # slot (-1 = base model), consumed by the fused decode program's
    # RaggedBatch construction (models/llama.py adapter_slots).
    adapter_slots: object = None  # [B] int32 | None


def _filtered_logits(
    scaled: jnp.ndarray,  # [B, V] temperature-scaled logits
    top_k: jnp.ndarray,  # [B] int32; 0 → disabled
    top_p: jnp.ndarray,  # [B] f32; 1.0 → disabled
) -> jnp.ndarray:
    """Apply top-k then top-p masks using a single descending sort."""
    B, V = scaled.shape
    sorted_desc = jnp.sort(scaled, axis=-1)[:, ::-1]  # [B, V]

    # top-k: mask everything below the k-th largest logit.
    k = jnp.clip(jnp.where(top_k <= 0, V, top_k), 1, V)
    kth = jnp.take_along_axis(sorted_desc, (k - 1)[:, None], axis=-1)  # [B, 1]

    # The top-k-masked copy stays sorted: positions >= k become NEG_INF.
    idx = jnp.arange(V, dtype=jnp.int32)[None, :]
    sorted_masked = jnp.where(idx < k[:, None], sorted_desc, NEG_INF)

    # top-p: keep the smallest prefix of the sorted distribution with
    # cumulative probability >= top_p (the kept set always includes argmax).
    probs_sorted = jax.nn.softmax(sorted_masked, axis=-1)
    cum = jnp.cumsum(probs_sorted, axis=-1)
    cutoff_count = jnp.sum(cum - probs_sorted < top_p[:, None], axis=-1)  # [B]
    cutoff_count = jnp.clip(cutoff_count, 1, V)
    thresh = jnp.take_along_axis(
        sorted_masked, (cutoff_count - 1)[:, None], axis=-1
    )

    scaled = jnp.where(scaled >= kth, scaled, NEG_INF)
    return jnp.where(scaled >= thresh, scaled, NEG_INF)


def _row_keys(seeds: jnp.ndarray, steps: jnp.ndarray) -> jnp.ndarray:
    """[B] independent PRNG keys: fold_in(PRNGKey(seed), step)."""

    def one(seed, step):
        return jax.random.fold_in(jax.random.PRNGKey(seed), step)

    return jax.vmap(one)(seeds.astype(jnp.uint32), steps.astype(jnp.uint32))


def sample_tokens(
    logits: jnp.ndarray,  # [B, V] f32
    seeds: jnp.ndarray,  # [B] uint32 per-request seed
    steps: jnp.ndarray,  # [B] int32 output-token index (rng stream position)
    temperature: jnp.ndarray,  # [B] f32; 0 → greedy
    top_k: jnp.ndarray,  # [B] int32; 0 → disabled
    top_p: jnp.ndarray,  # [B] f32; 1.0 → disabled
    freq_penalty: jnp.ndarray,  # [B] f32; 0 → disabled
    pres_penalty: jnp.ndarray,  # [B] f32; 0 → disabled
    counts: jnp.ndarray,  # [B, V] int16 output-token counts (penalties)
    need_logprobs: jnp.ndarray,  # [] bool — any row wants logprobs
    mask_words: Optional[jnp.ndarray] = None,  # [B, ceil(V/32)] uint32
    any_mask: Optional[jnp.ndarray] = None,  # [] bool — any row masked
) -> SampleOut:
    """Sample one token per row; optionally raw logprobs of the choice.

    ``mask_words`` (grammar-constrained decoding) is a packed per-row
    admissible-token bitmask: inadmissible logits drop to NEG_INF BEFORE
    temperature/top-k/top-p, so greedy and seeded sampling both draw from
    exactly the admissible distribution (per-(seed, step) determinism is
    untouched — same key, same step, masked logits).  Rows whose mask is
    all-ones are unconstrained; the whole unpack is cond-skipped when
    ``any_mask`` is false.  Reported logprobs stay the RAW model
    distribution (OpenAI semantics), pre-penalty and pre-mask.
    """
    B, V = logits.shape

    def penalized() -> jnp.ndarray:
        c = counts.astype(jnp.float32)
        return logits - freq_penalty[:, None] * c - pres_penalty[:, None] * (
            c > 0
        )

    any_pen = jnp.any((freq_penalty != 0.0) | (pres_penalty != 0.0))
    eff = lax.cond(any_pen, penalized, lambda: logits)

    if mask_words is not None and any_mask is not None:

        def masked() -> jnp.ndarray:
            # [B, W] uint32 → [B, W, 32] bits → [B, W*32] → [:, :V]
            shifts = jnp.arange(32, dtype=jnp.uint32)
            bits = (mask_words[:, :, None] >> shifts[None, None, :]) & jnp.uint32(1)
            admissible = bits.reshape(B, -1)[:, :V] != 0
            return jnp.where(admissible, eff, NEG_INF)

        eff = lax.cond(jnp.asarray(any_mask, jnp.bool_), masked, lambda: eff)

    greedy = jnp.argmax(eff, axis=-1).astype(jnp.int32)
    temp = jnp.maximum(temperature, 1e-6)[:, None]

    def cat(scaled: jnp.ndarray) -> jnp.ndarray:
        # Key derivation lives INSIDE the sampling branches: on an
        # all-greedy step (the decode hot path for benchmark and batch
        # traffic) the outer lax.cond takes the greedy branch and the
        # per-row threefry fold_in work is skipped entirely — at batch 256
        # x decode_steps per fused dispatch that was real device work spent
        # deriving keys nothing consumed.
        keys = _row_keys(seeds, steps)
        return jax.vmap(
            lambda k, row: jax.random.categorical(k, row)
        )(keys, scaled).astype(jnp.int32)

    def sample_filtered() -> jnp.ndarray:
        sampled = cat(_filtered_logits(eff / temp, top_k, top_p))
        return jnp.where(temperature <= 0.0, greedy, sampled)

    def sample_plain() -> jnp.ndarray:
        sampled = cat(eff / temp)
        return jnp.where(temperature <= 0.0, greedy, sampled)

    need_filter = jnp.any(
        (temperature > 0.0) & ((top_k > 0) | (top_p < 1.0))
    )
    tokens = lax.cond(
        jnp.any(temperature > 0.0),
        lambda: lax.cond(need_filter, sample_filtered, sample_plain),
        lambda: greedy,
    )

    def with_logprobs():
        # Raw model distribution (pre-penalty, pre-temperature) — the
        # OpenAI-reported quantity.
        k = min(TOPK_LOGPROBS, V)
        logp = jax.nn.log_softmax(logits, axis=-1)
        chosen = jnp.take_along_axis(logp, tokens[:, None], axis=-1)[:, 0]
        top_lp, top_ids = lax.top_k(logp, k)
        pad = TOPK_LOGPROBS - k  # tiny test vocabs: stable output width
        if pad:
            top_lp = jnp.pad(top_lp, ((0, 0), (0, pad)), constant_values=NEG_INF)
            top_ids = jnp.pad(top_ids, ((0, 0), (0, pad)))
        return chosen, top_ids.astype(jnp.int32), top_lp

    def without_logprobs():
        return (
            jnp.zeros((B,), jnp.float32),
            jnp.zeros((B, TOPK_LOGPROBS), jnp.int32),
            jnp.zeros((B, TOPK_LOGPROBS), jnp.float32),
        )

    chosen, top_ids, top_lp = lax.cond(
        need_logprobs, with_logprobs, without_logprobs
    )
    return SampleOut(tokens, chosen, top_ids, top_lp)

"""Token sampling: greedy / temperature / top-k / top-p, fully vectorized.

One jitted function handles a mixed batch (each sequence has its own
temperature/top-k/top-p/seed) and stays one XLA program.  What a step pays
follows what its rows ask for, decided on the device from the parameter
arrays the program already holds (``lax.cond``: one branch runs): a batch
in which no row samples takes the argmax and nothing else, one in which a
row samples draws, and sorts the vocabulary (once, for both filters) only
if a sampling row set top-k or top-p.  Within a sampling batch the
greedy-vs-sampled choice per row is a ``jnp.where``.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def needs_sort(temperature, top_p, top_k):
    """Does a row that samples ask for a filter over the sorted vocabulary?
    Operators only: the device predicate (jax arrays) and the host's
    counter of it (numpy, ``tpu:sample_sorted_dispatch_total``) are this
    one expression."""
    return ((temperature > 0) & ((top_k > 0) | (top_p < 1.0))).any()


def _apply_top_k_top_p(
    logits: jax.Array, top_k: jax.Array, top_p: jax.Array
) -> jax.Array:
    """Mask logits below the k-th largest (top_k<=0 disables), then nucleus
    filtering of what is left (top_p>=1 disables), from ONE descending sort.
    The top-k mask is monotone, so applied to the sorted row it gives the
    sorted form of the masked row: what a second sort would return."""
    V = logits.shape[-1]
    use_k = (top_k > 0)[:, None]
    sorted_desc = jnp.sort(logits, axis=-1)[..., ::-1]  # [S, V]
    k = jnp.clip(top_k, 1, V)
    kth = jnp.take_along_axis(sorted_desc, (k - 1)[:, None], axis=-1)  # [S,1]
    masked = jnp.where(logits < kth, NEG_INF, logits)
    logits = jnp.where(use_k, masked, logits)
    masked = jnp.where(sorted_desc < kth, NEG_INF, sorted_desc)
    sorted_desc = jnp.where(use_k, masked, sorted_desc)

    probs = jax.nn.softmax(sorted_desc, axis=-1)
    cumulative = jnp.cumsum(probs, axis=-1)
    # Keep tokens whose cumulative mass (exclusive) is below top_p; the
    # first token is always kept.
    keep = (cumulative - probs) < top_p[:, None]
    # Smallest kept logit is the threshold.
    threshold = jnp.min(
        jnp.where(keep, sorted_desc, jnp.inf), axis=-1, keepdims=True
    )
    masked = jnp.where(logits < threshold, NEG_INF, logits)
    return jnp.where((top_p < 1.0)[:, None], masked, logits)


def _apply_min_p(logits: jax.Array, min_p: jax.Array) -> jax.Array:
    """vLLM min_p: drop tokens whose probability is below
    ``min_p * max_prob``.  min_p<=0 disables."""
    probs = jax.nn.softmax(logits, axis=-1)
    cut = jnp.max(probs, axis=-1, keepdims=True) * min_p[:, None]
    masked = jnp.where(probs < cut, NEG_INF, logits)
    return jnp.where((min_p > 0)[:, None], masked, logits)


def sample_tokens(
    logits: jax.Array,  # [S, V] fp32
    temperature: jax.Array,  # [S]
    top_p: jax.Array,  # [S]
    top_k: jax.Array,  # [S] int32
    step_key: jax.Array,  # PRNG key
    seq_seeds: jax.Array,  # [S] int32 per-sequence seed fold
    min_p: Optional[jax.Array] = None,  # [S]; None -> disabled
) -> jax.Array:
    """Returns sampled token ids [S] (int32)."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    samples = temperature > 0

    def draw():
        safe_temp = jnp.where(samples, temperature, 1.0)
        scaled = logits / safe_temp[:, None]
        scaled = jax.lax.cond(
            needs_sort(temperature, top_p, top_k),
            lambda x: _apply_top_k_top_p(x, top_k, top_p),
            lambda x: x,
            scaled,
        )
        if min_p is not None:
            scaled = _apply_min_p(scaled, min_p)
        keys = jax.vmap(lambda s: jax.random.fold_in(step_key, s))(seq_seeds)
        sampled = jax.vmap(
            lambda key, row: jax.random.categorical(key, row)
        )(keys, scaled).astype(jnp.int32)
        return jnp.where(samples, sampled, greedy)

    # Padded rows carry temperature 0, so the batch itself says when
    # nobody samples.
    return jax.lax.cond(samples.any(), draw, lambda: greedy)


def compute_logprobs(logits: jax.Array, token_ids: jax.Array) -> jax.Array:
    """Log-prob of the chosen tokens: [S, V], [S] -> [S]."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    return jnp.take_along_axis(logp, token_ids[:, None], axis=-1)[:, 0]


def occurrence_state(
    out_tokens: jax.Array,  # [S, L] int32 generated-so-far, -1 padded
    ctx_tokens: jax.Array,  # [S, Lc] int32 prompt+generated, -1 padded
    vocab_size: int,
):
    """Device-resident per-sequence token-occurrence state: the
    ``counts`` histogram over GENERATED tokens (int16 — the bounded
    per-token occurrence count feeding presence/frequency) and the
    ``seen`` bitmap over prompt AND generated tokens (repetition).
    Built by scatter from the small [S, L] id arrays; the K-step decode
    window carries both through its scan and updates them per sampled
    token, so penalties apply on-device with no host round-trip."""
    valid = out_tokens >= 0
    ids = jnp.where(valid, out_tokens, 0)
    counts = jax.vmap(
        lambda i, v: jnp.zeros((vocab_size,), jnp.int16).at[i].add(
            v.astype(jnp.int16)
        )
    )(ids, valid)
    cvalid = ctx_tokens >= 0
    cids = jnp.where(cvalid, ctx_tokens, 0)
    seen = jax.vmap(
        lambda i, v: jnp.zeros((vocab_size,), jnp.bool_).at[i].max(v)
    )(cids, cvalid)
    return counts, seen


def apply_penalties_state(
    logits: jax.Array,  # [S, V] fp32
    counts: jax.Array,  # [S, V] int16 generated-token occurrence counts
    seen: jax.Array,  # [S, V] bool prompt+generated occurrence bitmap
    presence: jax.Array,  # [S]
    frequency: jax.Array,  # [S]
    repetition: jax.Array,  # [S]; 1.0 = off
) -> jax.Array:
    """The ONE place the penalty math lives (host single-step path and
    the K-step decode window both land here, so the two can never
    diverge).  HF/vLLM ``repetition_penalty`` over prompt AND generated
    tokens applies to the RAW logits first (for every seen token,
    positive logits divide by the penalty, negative multiply — HF
    ``RepetitionPenaltyLogitsProcessor``), then the OpenAI
    presence/frequency penalties over the GENERATED tokens (vLLM
    semantics: the prompt is not penalized).  Per sequence:
    ``logit[t] -= presence*[count(t)>0] + frequency*count(t)``.

    Order matters when both families hit the same token (HF/vLLM apply
    repetition before the subtraction: logit 2.0, presence 1.5, rep 2.0
    must give -0.5, not +0.25).  With penalties off the result is
    bit-identical to the input (x/1.0, x*1.0 and x-0.0 are exact)."""
    rep = repetition[:, None]
    scaled = jnp.where(logits > 0, logits / rep, logits * rep)
    logits = jnp.where(seen, scaled, logits)
    countsf = counts.astype(jnp.float32)
    penalty = presence[:, None] * (countsf > 0) + frequency[:, None] * countsf
    return logits - penalty


def apply_penalties(
    logits: jax.Array,  # [S, V] fp32
    out_tokens: jax.Array,  # [S, L] int32 generated-so-far, -1 padded
    presence: jax.Array,  # [S]
    frequency: jax.Array,  # [S]
    repetition: jax.Array = None,  # [S]; 1.0 = off
    ctx_tokens: jax.Array = None,  # [S, Lc] prompt+generated, -1 padded
) -> jax.Array:
    """Single-step host-path entry: build the occurrence state from the
    per-step token-id arrays, then apply the shared penalty math.
    ``repetition=None`` skips the seen-bitmap build entirely (the
    common presence/frequency-only batch)."""
    S, V = logits.shape
    if repetition is not None:
        counts, seen = occurrence_state(
            out_tokens,
            ctx_tokens if ctx_tokens is not None else out_tokens,
            V,
        )
        return apply_penalties_state(
            logits, counts, seen, presence, frequency, repetition
        )
    valid = out_tokens >= 0
    ids = jnp.where(valid, out_tokens, 0)
    counts = jax.vmap(
        lambda i, v: jnp.zeros((V,), jnp.float32).at[i].add(
            v.astype(jnp.float32)
        )
    )(ids, valid)
    penalty = presence[:, None] * (counts > 0) + frequency[:, None] * counts
    return logits - penalty


def top_logprobs_of(
    logits: jax.Array,  # [S, V] fp32
    token_ids: jax.Array,  # [S] chosen tokens
    k: int,
):
    """Chosen-token logprob + top-k alternatives (OpenAI ``logprobs``).
    Returns (chosen [S], top_ids [S, k], top_logps [S, k])."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    chosen = jnp.take_along_axis(logp, token_ids[:, None], axis=-1)[:, 0]
    top_logps, top_ids = jax.lax.top_k(logp, k)
    return chosen, top_ids.astype(jnp.int32), top_logps

"""Token samplers: greedy / temperature / top-k / top-p, per-sequence
PRNG.

One vmapped `sample_tokens` serves every lane of the decode batch in a
single fused call — per-lane sampling params ride as arrays, so mixed
greedy/top-k/top-p batches still hit one compiled executable
(fixed-shape, like everything else in the generation engine).

Determinism contract (tests/test_generation.py pins it): a sequence's
tokens are a pure function of (logits stream, seed, step index) — the
key is fold_in(PRNGKey(seed), step), never split statefully — so an
evicted-and-replayed sequence regenerates its prefix bitwise and a
re-run with the same seed reproduces the same text regardless of which
batch-mates shared its decode steps.

That same contract is what makes speculative decoding EXACT (PR 14,
engine._mixed_once): a verify slot for draft position j samples with
step = the absolute token index it would have in plain decode, and the
vmapped rows are independent, so when the drafts feeding it were all
accepted its logits AND its key match the plain-decode step — the
emitted token is bitwise the plain-decode token, by induction over the
accepted prefix. Rejection needs no sampler rollback: later steps
re-sample the same indices with the same fold_in keys.

The batch-level branch (PR 30). Under vmap a lane's `temp <= 0` is a
select: every lane pays the sorts, the softmax, the cumulative sum,
the scatter and the draw, greedy or not (31 ms of a 49 ms step at
`[32, 50257]` on a v5e, for traffic that asked for none of it). So
`sample_tokens` computes ONE scalar on the device from the step's own
input, `any(temps > 0)`, and hands it to `lax.cond`: false runs the
argmax and nothing else, true runs the vmapped lanes as before. It is
one algorithm and one executable: no second program, no flag, no
choice on the host. The contract holds bit for bit: a greedy lane
yields the same argmax in both branches, and a sampled lane always
takes the branch it took before. There is NO middle tier (temperature
without filters drawing straight from the scaled logits): at top_p
1.0 the filter path can still drop tail tokens when the cumulative
sum rounds to 1.0, so such a lane's token would depend on whether a
batch-mate asked for a filter.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

__all__ = ["SamplingParams", "sample_tokens"]

# finite -inf for logit masking, same convention as the attention
# kernels (kernels/paged_attention.NEG_INF)
_NEG_INF = -1e30


@dataclass(frozen=True)
class SamplingParams:
    """Per-request sampling configuration.

    temperature <= 0 means greedy (argmax; top_k/top_p/seed ignored).
    top_k 0 disables the k-filter; top_p >= 1.0 disables the nucleus
    filter. Both filters compose (k first, then p), matching the usual
    serving semantics."""
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    seed: int = 0

    def __post_init__(self):
        if self.top_k < 0:
            raise ValueError("top_k must be >= 0")
        if not (0.0 < self.top_p <= 1.0):
            raise ValueError("top_p must be in (0, 1]")


def _sample_one(logits, temp, top_k, top_p, seed, step):
    """One lane: logits [V] -> token (int32). Traced under vmap; every
    branch is a where-select so lanes with different settings share the
    executable."""
    v = logits.shape[-1]
    greedy_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    # temperature (guard temp<=0: greedy lane, value unused)
    scaled = logits / jnp.maximum(temp, 1e-6)

    # top-k: keep lanes scoring >= the k-th largest. top_k == 0 keeps
    # everything. Clamp to [1, V]; kth value via sorted descending.
    k = jnp.clip(jnp.where(top_k == 0, v, top_k), 1, v)
    sorted_desc = jnp.sort(scaled)[::-1]
    kth = sorted_desc[k - 1]
    filtered = jnp.where(scaled >= kth, scaled, _NEG_INF)

    # top-p (nucleus): over the survivors, keep the smallest prefix of
    # the descending-probability order whose mass reaches top_p. The
    # EXCLUSIVE cumulative sum keeps every token whose predecessors
    # haven't already covered p — so the boundary token that crosses p
    # stays in, and at least one token always survives.
    probs = jax.nn.softmax(filtered)
    order = jnp.argsort(-probs)
    csum_excl = jnp.cumsum(probs[order]) - probs[order]
    keep_sorted = csum_excl < top_p
    keep = jnp.zeros((v,), bool).at[order].set(keep_sorted)
    filtered = jnp.where(keep, filtered, _NEG_INF)

    key = jax.random.fold_in(jax.random.PRNGKey(seed), step)
    sampled_tok = jax.random.categorical(key, filtered).astype(jnp.int32)
    return jnp.where(temp <= 0.0, greedy_tok, sampled_tok)


@partial(jax.jit, static_argnames=())
def sample_tokens(logits, temps, top_ks, top_ps, seeds, steps):
    """Batched sampler: logits `[B, V]`, everything else `[B]`
    (float32 temps/top_ps, int32 top_ks/seeds/steps). Returns `[B]`
    int32 tokens. `steps` is each lane's OWN decode-step counter (its
    position in its sequence), which is what makes eviction replay and
    batch-composition independence work. A batch whose every lane is
    greedy (temps all <= 0; the engine fills unused lanes so) takes the
    argmax and skips the filter path whole: the branch is decided on
    the device, inside the one executable."""
    temps = temps.astype(jnp.float32)
    return jax.lax.cond(
        jnp.any(temps > 0.0),
        jax.vmap(_sample_one),
        lambda logits, *_: jnp.argmax(logits, axis=-1).astype(jnp.int32),
        logits, temps, top_ks.astype(jnp.int32),
        top_ps.astype(jnp.float32), seeds.astype(jnp.int32),
        steps.astype(jnp.int32))

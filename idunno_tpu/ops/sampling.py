"""Shared sampling transforms for the generation tiers.

One implementation of top-k and nucleus (top-p) filtering serves both
one-shot `engine.generate` and the continuous-batching pool
(`engine.serve_lm`) — the pool's token-exactness contract depends on the
two tiers filtering identically, so the construction lives here once.
Two forms of it:

- `sample_keep_mask`/`masked_sample_logits`: the TOKEN-exact hot path
  (generate loop, `fused_decode_tail`, the prefill pick). Thresholds
  come from exact bit-bisection over f32 patterns, so the whole tail is
  elementwise ops + per-row reductions — GSPMD partitions it over a
  vocab-sharded unembed without all-gathering [rows, vocab] logits
  (ISSUE 16).
- `filtered_probs`/`nucleus_probs`: the sort-based NORMALIZED
  distribution, the plain statement of the filter. No program path
  calls it: it is the reference `tests/test_sampling.py` holds
  `sample_keep_mask`'s survivor set to.

Reference has no sampling at all (`alexnet_resnet.py` serves argmax
classifications only).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _nucleus_on_probs(probs: jnp.ndarray,
                      top_p: jnp.ndarray) -> jnp.ndarray:
    """Nucleus-filter an (already normalized) probability tensor over the
    LAST axis. The nucleus is the smallest sorted-probability prefix whose
    mass reaches top_p, with the target clamped to the achievable float32
    cumsum total so round-off near 1.0 can't collapse the nucleus to the
    argmax token. top_p >= 1 is the identity."""
    sorted_p = jnp.flip(jnp.sort(probs, axis=-1), axis=-1)
    cum = jnp.cumsum(sorted_p, axis=-1)
    target = jnp.minimum(top_p[..., None], cum[..., -1:])
    k_idx = jnp.argmax(cum >= target, axis=-1)
    cutoff = jnp.take_along_axis(sorted_p, k_idx[..., None], axis=-1)
    keep = (probs >= cutoff) | (top_p[..., None] >= 1.0)
    filt = jnp.where(keep, probs, 0.0)
    return filt / filt.sum(axis=-1, keepdims=True)


def nucleus_probs(scaled_logits: jnp.ndarray,
                  top_p: jnp.ndarray) -> jnp.ndarray:
    """Temperature-scaled logits → nucleus-filtered, renormalized
    probabilities over the LAST axis (any leading shape; ``top_p``
    broadcasts over it)."""
    return _nucleus_on_probs(jax.nn.softmax(scaled_logits, axis=-1), top_p)


def filtered_probs(scaled_logits: jnp.ndarray, top_p: jnp.ndarray,
                   top_k: jnp.ndarray) -> jnp.ndarray:
    """Temperature-scaled logits → top-k then nucleus filtered,
    renormalized probabilities over the LAST axis.

    ``top_k`` is integer (0 or >= vocab disables the k-filter); ``top_p``
    as in `nucleus_probs`; both broadcast over the leading shape. Filter
    order matches the standard sequential-warper convention: the k
    largest tokens are kept first (ties AT the k-th probability are all
    kept — the filter is a probability threshold, so equal-probability
    tokens are indistinguishable), then the nucleus is taken over the
    RENORMALIZED top-k distribution. With both filters off this is the
    plain softmax."""
    probs = jax.nn.softmax(scaled_logits, axis=-1)
    v = probs.shape[-1]
    k = jnp.clip(top_k, 0, v)
    # ONE descending sort serves both filters: the top-k survivors are
    # exactly the prefix of sorted_p at/above the k-th probability, and
    # k-masking preserves sort order,
    # so the nucleus cutoff over the RENORMALIZED top-k distribution is
    # derivable from the same sorted array — cumsum of the masked prefix
    # divided by its total is the normalized cumulative the nucleus
    # construction needs.
    sorted_p = jnp.flip(jnp.sort(probs, axis=-1), axis=-1)
    idx = jnp.clip(k - 1, 0, v - 1)
    kth = jnp.take_along_axis(
        sorted_p, jnp.broadcast_to(idx[..., None], probs.shape[:-1] + (1,)),
        axis=-1)
    k_off = (k[..., None] <= 0) | (k[..., None] >= v)
    keep_k = (probs >= kth) | k_off
    masked_sorted = jnp.where((sorted_p >= kth) | k_off, sorted_p, 0.0)
    z = masked_sorted.sum(axis=-1, keepdims=True)
    cum = jnp.cumsum(masked_sorted, axis=-1) / z
    target = jnp.minimum(top_p[..., None], cum[..., -1:])
    k_idx = jnp.argmax(cum >= target, axis=-1)
    cutoff = jnp.take_along_axis(masked_sorted, k_idx[..., None], axis=-1)
    keep = keep_k & ((probs >= cutoff) | (top_p[..., None] >= 1.0))
    filt = jnp.where(keep, probs, 0.0)
    return filt / filt.sum(axis=-1, keepdims=True)


# float32 1.0 bit pattern: the bisection space for values in [0, 1]
_ONE_BITS = 0x3F800000


def _largest_true_bits(pred, rows: tuple) -> jnp.ndarray:
    """Largest f32 ``t`` in [0, nextafter(1)] with ``pred(t)`` True, per
    row. Non-negative IEEE floats order like their int32 bit patterns,
    so an exact binary search over the bit space finds the exact float
    where a monotone (non-increasing) predicate flips — no sort, no
    cumsum, only the elementwise compares and small reductions ``pred``
    itself makes. 31 rounds cover the ~2^30-wide pattern range."""
    lo = jnp.zeros(rows, jnp.int32)
    hi = jnp.full(rows, _ONE_BITS + 1, jnp.int32)

    def body(_, lh):
        lo, hi = lh
        mid = (lo + hi) >> 1
        ok = pred(jax.lax.bitcast_convert_type(mid, jnp.float32))
        return jnp.where(ok, mid, lo), jnp.where(ok, hi, mid)

    lo, hi = jax.lax.fori_loop(0, 31, body, (lo, hi))
    return jax.lax.bitcast_convert_type(lo, jnp.float32)


def sample_keep_mask(scaled: jnp.ndarray, top_p: jnp.ndarray,
                     top_k: jnp.ndarray) -> jnp.ndarray:
    """Top-k + nucleus survivor mask over the LAST axis, in the
    partition-friendly form the vocab-sharded tail needs (ISSUE 16).

    Selects the same set as ``filtered_probs(scaled, top_p, top_k) > 0``
    — the k largest tokens (ties AT the k-th value all kept), then the
    smallest prefix of the renormalized top-k mass reaching ``top_p``
    (ties at the cutoff kept; an unreachable target degrades to the
    achievable mass automatically) — but computes its two thresholds by
    exact bit-bisection (`_largest_true_bits`) on the unnormalized
    softmax numerator ``e = exp(scaled - max)``:

      k-th value   = largest t with  count(e >= t)          >= k
      nucleus cut  = largest t with  mass(kept & e >= t)    >= top_p·Z

    Everything is elementwise ops + per-row reductions, so GSPMD
    partitions it over a sharded vocab axis with one small collective
    per reduction — no sort, cumsum, or take_along_axis to force an
    all-gather of the ``[rows, vocab]`` tensor. Working on ``e`` (not
    the normalized probs) keeps every comparison input elementwise —
    bitwise identical across mesh shapes; only the mass sums carry
    reduction-order rounding. Both generation tiers (`engine.generate`
    and the serving tail) build their masks here, so cross-tier
    token-exactness is structural."""
    v = scaled.shape[-1]
    rows = scaled.shape[:-1]
    e = jnp.exp((scaled - jnp.max(scaled, axis=-1, keepdims=True))
                .astype(jnp.float32))
    k = jnp.clip(top_k, 0, v)
    k_off = (k <= 0) | (k >= v)
    kth = _largest_true_bits(
        lambda t: jnp.sum(e >= t[..., None], axis=-1) >= k, rows)
    keep_k = (e >= kth[..., None]) | k_off[..., None]
    masked = jnp.where(keep_k, e, 0.0)
    z = jnp.sum(masked, axis=-1)
    # the tiny floor makes top_p→0 keep the argmax tie-set (the mass
    # predicate must fail above the largest kept value, not everywhere)
    target = jnp.maximum(top_p * z, jnp.float32(1e-38))
    cut = _largest_true_bits(
        lambda t: jnp.sum(jnp.where(masked >= t[..., None], masked, 0.0),
                          axis=-1) >= target, rows)
    p_off = top_p >= 1.0
    return keep_k & ((e >= cut[..., None]) | p_off[..., None])


def masked_sample_logits(scaled: jnp.ndarray, top_p: jnp.ndarray,
                         top_k: jnp.ndarray) -> jnp.ndarray:
    """Per-row sampling logits in the MASKED-SCALED form: filtered rows
    keep their scaled logits on the survivor set and -inf elsewhere;
    filter-off rows pass through untouched. `jax.random.categorical` is
    shift-invariant per row, so drawing from these equals drawing from
    ``log(filtered_probs)`` — without normalizing over the (possibly
    vocab-sharded) axis. The per-ROW select keeps every row's formula a
    function of its own request alone (journal replays redraw the same
    stream without former co-residents)."""
    keep = sample_keep_mask(scaled, top_p, top_k)
    off = ~filter_on(top_p, top_k)
    return jnp.where(keep | off[..., None], scaled, -jnp.inf)


def filter_on(top_p: jnp.ndarray, top_k: jnp.ndarray) -> jnp.ndarray:
    """Per-row: does this row ask for any sampling filter at all?"""
    return (top_p < 1.0) | (top_k > 0)


def fused_decode_tail(l_raw: jnp.ndarray, tokens: jnp.ndarray,
                      cursors: jnp.ndarray, remaining: jnp.ndarray,
                      temps: jnp.ndarray, top_ps: jnp.ndarray,
                      top_ks: jnp.ndarray, keys: jnp.ndarray,
                      logprobs: jnp.ndarray, pres: jnp.ndarray,
                      freq: jnp.ndarray, counts: jnp.ndarray, *,
                      max_len: int, eos_id: int | None, track: bool,
                      pen: bool) -> tuple:
    """The post-model tail of one continuous-batching decode step, fused
    into whatever jitted program calls it (`engine.serve_lm._build_decode`):
    penalties → temperature/top-k/top-p pick → token/logprob scatter →
    cursor/remaining/EOS bookkeeping → count update. ``l_raw`` is the raw
    [S, vocab] model logits for the step; returns ``(tokens, cursors,
    remaining, keys, logprobs, counts)``.

    The sampling machinery (per-row key split, temperature scale,
    log-softmax, gumbel draw) runs only when a LIVE row actually samples —
    an all-greedy pool (the common serving and bench case) skips the whole
    branch. Stream exactness: with any sampled live row the branch is the
    byte-identical math as always; without one, no row's output reads
    ``drawn`` (greedy picks argmax) and frozen keys are harmless (a
    retired sampled row never draws again; admission re-seeds the slot's
    key). ``track``/``pen``/``eos_id`` are compile-time flags — off means
    zero traced ops for that feature.

    Every op over the vocab axis is partition-friendly (ISSUE 16): the
    filter mask comes from `sample_keep_mask`, the draw/argmax are
    reductions GSPMD splits into shard-local stats + one small merge,
    the logprob pick is a one-hot sum and the count update an elementwise
    add — nothing sorts, cumsums, gathers, or scatters ``[S, vocab]``,
    so a vocab-sharded unembed (`parallel.sharding.lm_tp_specs`) flows
    through without an all-gather of the logits."""
    active = remaining > 0
    l = l_raw
    if pen:   # counts cover this row's GENERATED tokens only
        l = (l - pres[:, None] * (counts > 0)
             - freq[:, None] * counts.astype(l.dtype))

    def draw_sampled():
        # per-row key advance + sampled pick (row streams stay
        # independent of co-resident rows and of admissions)
        split = jax.vmap(lambda k: jax.random.split(k, 2))(keys)
        scaled = l / jnp.maximum(temps, 1e-6)[:, None]
        # the threshold bisections only run when some live row actually
        # asked for a filter; inside that branch the PER-ROW select in
        # `masked_sample_logits` passes unfiltered rows their untouched
        # scaled logits — identical to the other branch — so no row's
        # stream ever depends on its co-residents (token-exact journal
        # replay). categorical's shift-invariance makes the masked-scaled
        # form draw the same tokens `generate` draws from its own
        # identically-built mask.
        sample_logits = jax.lax.cond(
            jnp.any((remaining > 0) & (temps > 0.0)
                    & filter_on(top_ps, top_ks)),
            lambda: masked_sample_logits(scaled, top_ps, top_ks),
            lambda: scaled)
        d = jax.vmap(jax.random.categorical)(
            split[:, 0], sample_logits).astype(jnp.int32)
        return d, split[:, 1]

    drawn, keys = jax.lax.cond(
        jnp.any((remaining > 0) & (temps > 0.0)),
        draw_sampled,
        lambda: (jnp.zeros(tokens.shape[0], jnp.int32), keys))
    nxt = jnp.where(temps > 0.0, drawn,
                    jnp.argmax(l, axis=-1).astype(jnp.int32))
    wpos = jnp.clip(cursors + 1, 0, max_len - 1)
    old = jnp.take_along_axis(tokens, wpos[:, None], axis=1)[:, 0]
    rows = jnp.arange(tokens.shape[0])
    tokens = tokens.at[rows, wpos].set(jnp.where(active, nxt, old))
    if track:
        # logprobs report the RAW model distribution even on penalized
        # rows (sampler-independent semantics). Same float composition
        # as log_softmax + take_along_axis, but the pick is a one-hot
        # sum — summing one value against zeros is exact — so nothing
        # gathers over the vocab axis
        l32 = l_raw.astype(jnp.float32)
        shifted = l32 - jnp.max(l32, axis=-1, keepdims=True)
        lse = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1))
        iota = jnp.arange(l32.shape[-1])
        lp = jnp.sum(jnp.where(iota[None, :] == nxt[:, None],
                               shifted, 0.0), axis=-1) - lse
        lp_old = jnp.take_along_axis(logprobs, wpos[:, None], axis=1)[:, 0]
        logprobs = logprobs.at[rows, wpos].set(
            jnp.where(active, lp, lp_old))
    cursors = jnp.where(active, cursors + 1, cursors)
    new_remaining = remaining - 1
    if eos_id is not None:
        new_remaining = jnp.where(nxt == eos_id, 0, new_remaining)
    remaining = jnp.where(active, new_remaining, remaining)
    if pen:
        iota_v = jnp.arange(counts.shape[-1])
        hit = (iota_v[None, :] == nxt[:, None]) & active[:, None]
        counts = counts + hit.astype(counts.dtype)
    return tokens, cursors, remaining, keys, logprobs, counts

"""The two statements of the sampling filter (`ops/sampling.py`) agree.

`sample_keep_mask` is what every sampling path of the program runs (the
generate loop, the prefill pick, `fused_decode_tail`): thresholds by
bisection, no sort. `filtered_probs` / `nucleus_probs` state the same
filter the plain way, by a sort and a cumulative sum; no program path calls
them. The mask's docstring promises it selects the support of
`filtered_probs`; these tests hold it to that.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from idunno_tpu.ops.sampling import (filtered_probs, nucleus_probs,
                                     sample_keep_mask)

VOCAB = 37
ROWS = 6


def _scaled_logits() -> jnp.ndarray:
    """[ROWS, VOCAB] logits, flat rows to peaked ones (temperatures
    0.3-3 over one draw), no two values of a row equal."""
    base = jax.random.normal(jax.random.PRNGKey(31), (ROWS, VOCAB))
    return base * jnp.asarray([0.3, 0.7, 1.0, 1.5, 2.0, 3.0])[:, None]


def _clear_of_target(scaled, top_p, top_k) -> bool:
    """No cumulative mass of the renormalized top-k distribution lies
    within 1e-4 of ``top_p``: the sort's float32 sums and the bisection's
    may then round differently without choosing another set."""
    p = np.sort(np.asarray(jax.nn.softmax(scaled, axis=-1),
                           np.float64), axis=-1)[:, ::-1]
    if 0 < top_k < p.shape[-1]:
        p = p[:, :top_k]
    cum = np.cumsum(p / p.sum(-1, keepdims=True), axis=-1)
    return bool(np.abs(cum - top_p).min() > 1e-4)


def _support(scaled, top_p, top_k) -> np.ndarray:
    return np.asarray(filtered_probs(scaled, top_p, top_k)) > 0.0


def _mask(scaled, top_p, top_k) -> np.ndarray:
    return np.asarray(sample_keep_mask(scaled, top_p, top_k))


@pytest.mark.parametrize("top_k", [0, 1, 5, VOCAB])
@pytest.mark.parametrize("top_p", [1.0, 0.9, 0.6, 0.05])
def test_keep_mask_is_the_support_of_filtered_probs(top_p, top_k):
    scaled = _scaled_logits()
    assert top_p == 1.0 or _clear_of_target(scaled, top_p, top_k)
    ps = jnp.full((ROWS,), top_p, jnp.float32)
    ks = jnp.full((ROWS,), top_k, jnp.int32)
    want = _support(scaled, ps, ks)
    got = _mask(scaled, ps, ks)
    assert (got == want).all(), np.argwhere(got != want)
    kept = want.sum(-1)
    if top_p == 1.0 and top_k in (0, VOCAB):
        assert (kept == VOCAB).all()              # both filters off
    else:
        assert (kept < VOCAB).all() and (kept >= 1).all()
        assert top_k in (0, VOCAB) or (kept <= top_k).all()


@pytest.mark.parametrize("case, logits, top_p, top_k, kept", [
    # three tokens share the 2nd-largest value: k = 2 keeps all of them
    ("ties-at-kth", [3.0, 2.0, 2.0, 2.0, 1.0, 0.0, -1.0], 1.0, 2,
     [1, 1, 1, 1, 0, 0, 0]),
    # 0.4 alone misses 0.5; the cut falls on the first 0.2 and its twin
    # cannot be told from it
    ("ties-at-nucleus-cut", np.log([0.4, 0.2, 0.2, 0.1, 0.1]).tolist(),
     0.5, 0, [1, 1, 1, 0, 0]),
])
def test_keep_mask_keeps_ties_as_filtered_probs_does(case, logits, top_p,
                                                     top_k, kept):
    scaled = jnp.asarray([logits], jnp.float32)
    ps, ks = jnp.asarray([top_p], jnp.float32), jnp.asarray([top_k])
    want = _support(scaled, ps, ks)
    assert want[0].tolist() == [bool(k) for k in kept], case
    assert (_mask(scaled, ps, ks) == want).all(), case


def test_keep_mask_rows_depend_on_their_own_settings_alone():
    """Rows of mixed settings in one batch: each row's set is the support
    of `filtered_probs` under its own settings, and what it would be were
    it the only row."""
    scaled = _scaled_logits()
    ps = jnp.asarray([1.0, 0.9, 0.6, 0.05, 0.9, 1.0], jnp.float32)
    ks = jnp.asarray([0, 5, 0, VOCAB, 1, 5], jnp.int32)
    for r in range(ROWS):
        assert float(ps[r]) == 1.0 or _clear_of_target(
            scaled[r:r + 1], float(ps[r]), int(ks[r]))
    got = _mask(scaled, ps, ks)
    assert (got == _support(scaled, ps, ks)).all()
    for r in range(ROWS):
        alone = _mask(scaled[r:r + 1], ps[r:r + 1], ks[r:r + 1])
        assert (got[r] == alone[0]).all(), r
    assert len({int(n) for n in got.sum(-1)}) > 2     # the rows do differ


def test_nucleus_probs_masks_tail():
    """`nucleus_probs` keeps exactly the smallest prefix of sorted mass
    reaching top_p and renormalizes; top_p=1 is the identity."""
    logits = jnp.log(jnp.asarray([[0.5, 0.3, 0.15, 0.05]]))
    out = np.asarray(nucleus_probs(logits, jnp.asarray([0.6])))[0]
    # nucleus = {0.5, 0.3} (0.5 alone < 0.6) → renormalized 0.625/0.375
    assert np.allclose(out, [0.625, 0.375, 0.0, 0.0], atol=1e-6)
    ident = np.asarray(nucleus_probs(logits, jnp.asarray([1.0])))[0]
    assert np.allclose(ident, [0.5, 0.3, 0.15, 0.05], atol=1e-6)


def test_filtered_probs_top_k():
    """filtered_probs: top_k keeps the k most probable (renormalized),
    composes with the nucleus over the RENORMALIZED top-k distribution,
    and k=0 / k>=vocab are the identity."""
    logits = jnp.log(jnp.asarray([[0.5, 0.3, 0.15, 0.05]]))
    k2 = np.asarray(filtered_probs(logits, jnp.asarray([1.0]),
                                   jnp.asarray([2])))[0]
    assert np.allclose(k2, [0.625, 0.375, 0.0, 0.0], atol=1e-6)
    off = np.asarray(filtered_probs(logits, jnp.asarray([1.0]),
                                    jnp.asarray([0])))[0]
    assert np.allclose(off, [0.5, 0.3, 0.15, 0.05], atol=1e-6)
    big = np.asarray(filtered_probs(logits, jnp.asarray([1.0]),
                                    jnp.asarray([99])))[0]
    assert np.allclose(big, off, atol=1e-6)
    # k=3 then top_p=0.6 on the renormalized {0.526, 0.316, 0.158}:
    # nucleus = {0.526, 0.316} → 0.625/0.375
    both = np.asarray(filtered_probs(logits, jnp.asarray([0.6]),
                                     jnp.asarray([3])))[0]
    assert np.allclose(both, [0.625, 0.375, 0.0, 0.0], atol=1e-4)
    # pure-nucleus path unchanged by the refactor
    nuc = np.asarray(nucleus_probs(logits, jnp.asarray([0.6])))[0]
    assert np.allclose(nuc, [0.625, 0.375, 0.0, 0.0], atol=1e-6)

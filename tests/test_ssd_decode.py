"""The Mamba-2 decode step over the live rows (`ops/ssd_decode.py`), in
the TPU interpret mode on the CPU: the kernel the chip compiles, held to the
single-token branch of `models/hybrid.py:_ssd` on random float32 states.
That mode keeps the chip's pipeline (a block is fetched and written back
only where the index maps move, and a buffer nothing wrote holds NaN), so a
dead step that left the last live block, or an all-dead step that handed
back a block it never filled, fails here; the plain interpreter reads and
writes every block at every step and passes both.

Not to the bit: interpreted on the CPU, the kernel's body is one loop in
which the compiler contracts ``state * decay + dx * B`` into a fused
multiply-add (the product ``dx * B`` is not rounded on its own), while
`_ssd`'s program rounds it. So a live row's state may differ by one rounding
of that product, and ``y``, a sum over the state's N entries, by what that
carries (the bounds below). A dead row, and every other layer, is held to
the bit."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from jax.experimental.pallas import tpu as pltpu

from idunno_tpu.models.hybrid import _ssd
from idunno_tpu.ops import ssd_decode as k

EPS = float(np.finfo(np.float32).eps)

# (layers, slots, groups, heads a group, P, N): Falcon-H1's [2 groups x 16,
# 128, 256] and granite-4.0-h-small's [1 x 128, 64, 128], scaled down
GEOMETRIES = {"falcon-h1": (3, 6, 2, 4, 16, 32),
              "granite": (2, 6, 1, 8, 8, 16)}
MASKS = {"all-live": [1, 1, 1, 1, 1, 1], "all-dead": [0, 0, 0, 0, 0, 0],
         "one-live": [0, 0, 0, 1, 0, 0], "gaps": [1, 0, 1, 1, 0, 1]}


def _inputs(geometry, seed):
    nl, rows, g, e, p, n = geometry
    h = g * e
    rng = np.random.default_rng(seed)

    def f32(x):
        return jnp.asarray(x, jnp.float32)

    return (f32(rng.standard_normal((nl, rows, h, p, n))),
            f32(rng.uniform(1e-3, 0.5, (rows, h))),
            f32(rng.standard_normal((rows, h, p))),
            f32(rng.standard_normal((rows, g, n))),
            f32(rng.standard_normal((rows, g, n))),
            f32(-rng.uniform(1, 16, h)))


@pytest.mark.parametrize("mask", list(MASKS))
@pytest.mark.parametrize("geometry", list(GEOMETRIES))
@pytest.mark.parametrize("heads_a_block, heads_a_trip",
                         [(None, None), (2, None), (None, 2)],
                         ids=["wide", "narrow", "trips"])
def test_live_rows_step_and_dead_rows_stay(monkeypatch, geometry, mask,
                                           heads_a_block, heads_a_trip):
    """Layer 1 of the stack: each live row's state and ``y`` are `_ssd`'s
    (within the rounding the module docstring names); a dead row's state,
    and every row of the other layers, are the bits that went in; a dead
    row's ``y`` is 0. "narrow" cuts a group's heads into blocks of two, so
    that a dead step stays on a block that is not a slot's first; "trips"
    visits a block's heads two a trip of the kernel's loop (the scaled-down
    blocks otherwise take one trip)."""
    nl, rows, g, e, p, n = GEOMETRIES[geometry]
    if heads_a_block:
        monkeypatch.setattr(k, "_BLOCK_BYTES", heads_a_block * p * n * 4)
    if heads_a_trip:
        monkeypatch.setattr(k, "_HEADS_A_TRIP", heads_a_trip)
    h = g * e
    assert k.head_block(h, e, p * n * 4) == (heads_a_block or e)
    states, dt, x, bm, cm, a = _inputs(GEOMETRIES[geometry], seed=len(mask))
    live = jnp.asarray(MASKS[mask], bool)
    new, y = jax.jit(lambda *args: k.ssd_decode(
        *args, interpret=pltpu.InterpretParams()))(states, 1, live, dt, x,
                                                   bm, cm, a)
    y_ref, s_ref = _ssd(x.reshape(rows, 1, g, e, p),
                        dt.reshape(rows, 1, g, e), a.reshape(g, e),
                        bm[:, None], cm[:, None],
                        states[1].reshape(rows, g, e, p, n), 16)
    new, y, old = (np.asarray(v) for v in (new, y, states))
    s_ref = np.asarray(s_ref).reshape(rows, h, p, n)
    y_ref = np.asarray(y_ref).reshape(rows, h, p)
    on = np.asarray(live)
    # dead rows and the other layers: untouched, to the bit
    assert np.array_equal(new[1][~on], old[1][~on])
    assert np.array_equal(np.delete(new, 1, 0), np.delete(old, 1, 0))
    assert np.all(y[~on] == 0)
    # live rows: `_ssd`'s update, within one rounding of dx * B
    dxb = np.abs((np.asarray(dt)[..., None] * np.asarray(x))[..., None]
                 * np.repeat(np.asarray(bm), e, axis=1)[:, :, None, :])
    assert (new[1] != old[1])[on].all()
    assert np.all(np.abs(new[1] - s_ref)[on]
                  <= EPS * (dxb + np.abs(s_ref))[on])
    c = np.repeat(np.asarray(cm), e, axis=1)[:, :, None, :]
    bound = n * EPS * np.sum(np.abs(s_ref * c), axis=-1)
    assert np.all(np.abs(y - y_ref)[on] <= bound[on])


def test_head_blocks_at_the_served_widths():
    """2 MiB of state a grid step: 16 of Falcon-H1's heads of [128, 256]
    (two blocks a slot, one a group), 64 of granite-4.0-h-small's [64,
    128]; a head larger than the budget goes alone."""
    assert k.head_block(32, 16, 128 * 256 * 4) == 16
    assert k.head_block(128, 128, 64 * 128 * 4) == 64
    assert k.head_block(8, 8, 8 << 20) == 1


def test_a_geometry_that_does_not_tile_is_refused():
    states, dt, x, bm, cm, a = _inputs(GEOMETRIES["falcon-h1"], seed=0)
    live = jnp.ones(6, bool)
    with pytest.raises(ValueError, match="float32"):
        k.ssd_decode(states.astype(jnp.bfloat16), 0, live, dt, x, bm, cm, a,
                     interpret=True)
    with pytest.raises(ValueError, match="do not tile"):
        k.ssd_decode(states, 0, live, dt, x[:, :, :8], bm, cm, a,
                     interpret=True)
    with pytest.raises(ValueError, match="groups"):
        k.ssd_decode(states, 0, live, dt, x, jnp.zeros((6, 3, 32)),
                     jnp.zeros((6, 3, 32)), a, interpret=True)
    with pytest.raises(ValueError, match="groups"):
        k.head_block(6, 4, 64)

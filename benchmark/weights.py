"""Seeded weights, made on the device in one jitted call, in the type they
are served in. The benchmark makes them, not the program. A family
(`benchmark/families/<family>/weights.py`) states its tensors; the drawing
here is the same for every family."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def seed_key(seed: int, impl: str | None = None):
    """A key from any whole number: 31 bits seed the key, the rest fold in,
    so seeds past 2**31 neither overflow nor collide with their low bits."""
    seed = int(seed)
    key = jax.random.key(seed & 0x7FFFFFFF, impl=impl)
    return jax.random.fold_in(key, seed >> 31)


@partial(jax.jit, static_argnames=("spec", "dtype"))
def _make(key, spec: tuple, dtype) -> dict:
    def draw(k, shape, kind, scale):
        x = jax.random.normal(k, shape, jnp.float32) * scale
        return ((1.0 + x) if kind == "ln_scale" else x).astype(dtype)

    out = {}
    for i, (name, shape, kind, scale, stacked) in enumerate(spec):
        k = jax.random.fold_in(key, i)
        if stacked:
            # layer by layer, so that the float32 draw of a 2 GB tensor is
            # never whole on the device beside the weights
            out[name] = jax.lax.map(
                lambda kl: draw(kl, shape[1:], kind, scale),
                jax.random.split(k, shape[0]))
        else:
            out[name] = draw(k, shape, kind, scale)
    return out


def draw(spec, seed: int, dtype) -> dict:
    """{name: array} for ``spec``, a sequence of (name, shape, kind, scale,
    stacked): normal draws times ``scale`` (kind "ln_scale": 1 + the draw),
    tensor ``i`` from ``fold_in(key, i)``; a stacked tensor is drawn one
    leading index at a time."""
    # the rbg generator fills 3 B values in well under a second on a TPU;
    # the key is private to this call, the program's own keys are untouched
    return _make(seed_key(seed, impl="rbg"), tuple(spec), jnp.dtype(dtype))

"""Seeded weights, made on the device in one jitted call, in the type they
are served in. The benchmark makes them, not the program: the program gets
them laid out as its scanned decode pool holds them, the plain reference
reads the same arrays under the benchmark's own names."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from benchmark import counts


def shapes(cfg: dict) -> dict[str, tuple]:
    d = counts.dims(cfg)
    L, h, H, K, hd, f, V = (d["layers"], d["h"], d["heads"], d["kvh"],
                            d["hd"], d["ffn"], d["vocab"])
    return {
        "embed": (V, h),
        "ln1_s": (L, h), "ln1_b": (L, h),
        "wq": (L, h, H, hd), "bq": (L, H, hd),
        "wk": (L, h, K, hd), "bk": (L, K, hd),
        "wv": (L, h, K, hd), "bv": (L, K, hd),
        "wo": (L, H, hd, h), "bo": (L, h),
        "ln2_s": (L, h), "ln2_b": (L, h),
        "w_up": (L, h, f), "b_up": (L, f),
        "w_down": (L, f, h), "b_down": (L, h),
        "lnf_s": (h,), "lnf_b": (h,),
        "w_head": (h, V), "b_head": (V,),
    }


def _fan_in(name: str, shape: tuple) -> int:
    if name == "wo":
        return shape[1] * shape[2]
    if name == "w_head":
        return shape[0]
    return shape[1]


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


def make_weights(cfg: dict, seed: int) -> dict:
    """All weights of the configuration from ``seed``: kernels at
    1/sqrt(fan-in), biases and LayerNorm offsets at 0.02, LayerNorm scales
    at 1 +- 0.1, the embedding at 1 - so that no term of the block is a
    no-op that a faulty path could drop unseen."""
    dtype = jnp.dtype(cfg.get("as_run", {}).get("dtype", "bfloat16"))
    spec = []
    for name, shape in shapes(cfg).items():
        if name == "embed":
            kind, scale = "embed", 1.0
        elif name.endswith("_s"):
            kind, scale = "ln_scale", 0.1
        elif name.startswith("w"):
            kind, scale = "kernel", _fan_in(name, shape) ** -0.5
        else:
            kind, scale = "bias", 0.02
        stacked = len(shape) > 1 and shape[0] == cfg["num_hidden_layers"] \
            and name not in ("embed", "w_head")
        spec.append((name, shape, kind, scale, stacked))
    # the rbg generator fills 3 B values in well under a second on a TPU;
    # the key is private to this call, the program's own keys are untouched
    return _make(seed_key(seed, impl="rbg"), tuple(spec), dtype)


def program_params(w: dict) -> dict:
    """The same arrays as the program's scanned pool holds them
    (`models/transformer.py:stack_block_params`): no copy."""
    def kb(k, b):
        return {"kernel": w[k], "bias": w[b]}
    return {
        "embed": {"embedding": w["embed"]},
        "blocks": {
            "ln1": {"scale": w["ln1_s"], "bias": w["ln1_b"]},
            "attn": {"q": kb("wq", "bq"), "k": kb("wk", "bk"),
                     "v": kb("wv", "bv"), "out": kb("wo", "bo")},
            "ln2": {"scale": w["ln2_s"], "bias": w["ln2_b"]},
            "mlp_up": kb("w_up", "b_up"),
            "mlp_down": kb("w_down", "b_down"),
        },
        "ln_f": {"scale": w["lnf_s"], "bias": w["lnf_b"]},
        "head": kb("w_head", "b_head"),
    }

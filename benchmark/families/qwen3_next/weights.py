"""Qwen3-Next's tensors under the benchmark's own names: the plain reference
reads them as they are, `program.py` hands the same arrays to the program's
hybrid stack. Layers are stacked a RUN at a time (consecutive layers of one
kind, `r<i>_<name>`), which is how the program scans them, so that neither
side slices a stacked tensor (a slice would be a copy).

The projections keep the published layout: a Gated DeltaNet layer's
`in_proj_qkvz` is [hidden, key heads, q dk | k dk | v r dv | z r dv] and its
`in_proj_ba` [hidden, key heads, b r | a r] (r value heads a key head); a
gated-attention layer's `q_proj` is [hidden, heads, q head_dim | gate
head_dim]. Every norm of the published model but the Gated DeltaNet's output
norm computes `x / rms(x) * (1 + w)` with `w` stored around 0; the seeded
weights store `1 + w` (the `ln_scale` kind) and both sides multiply by it.

Of the routed experts only the share held here is drawn (`num_experts` of
the configuration, the first of the router's `published` count): the router
keeps every output."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from benchmark import weights

# the router's kernel at twice 1/sqrt(fan-in), as granite's: the ten
# largest of 512 logits of standard deviation 2 spread their softmax from
# about 0.05 to 0.3, as a trained router's do; at 1 the ten gates are
# nearly flat and a fault in which ten were taken would hardly show
ROUTER_GAIN = 2.0
# the queries' and keys' norm scales (stored 1 + w): q and k are normed a
# head, so their product over 256 channels / 16 has standard deviation 1
# whatever the projections' scale, and a softmax over thousands of keys
# would be nearly flat (attention's output the mean of some thousand random
# values, which no fault in it would move). At 2 each the scores have
# standard deviation 4 and rest on a few keys, as a trained head's do
QK_NORM_SCALE = 2.0
# the published initialisation of the delta rule's decay: A uniform in
# (0, 16) (A_log its logarithm), dt_bias ones
A_RANGE = (0.0, 16.0)
# blocks of the embedding's draw: 151936 rows = 16 x 9496, a multiple of
# the chip's 8-row tile, so that the blocks fold back into one table
# without a copy
_EMBED_BLOCKS = 16


def runs_of(cfg: dict) -> list[tuple[str, int]]:
    """[(kind, layers)] of the consecutive layers of one kind."""
    out: list[list] = []
    for kind in cfg["layer_types"]:
        if out and out[-1][0] == kind:
            out[-1][1] += 1
        else:
            out.append([kind, 1])
    return [(k, n) for k, n in out]


def delta_dims(cfg: dict) -> dict:
    kh, vh = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    if vh % kh:
        raise ValueError("linear_num_value_heads must be a multiple of "
                         "linear_num_key_heads")
    r = vh // kh
    return {"kh": kh, "vh": vh, "dk": dk, "dv": dv, "r": r,
            "taps": cfg["linear_conv_kernel_dim"],
            "conv": 2 * kh * dk + vh * dv, "qkvz": 2 * dk + 2 * r * dv}


def spec(cfg: dict) -> tuple:
    """(name, shape, kind, scale, stacked) of every tensor `weights.draw`
    draws, in order: kernels at 1/sqrt(fan-in), norm scales 1 (`1 + w`,
    w = 0 as published), the embedding at 1 (no multiplier: the residual
    stream starts at order 1), the untied head at 1/sqrt(hidden)."""
    h, f, fs, V = (cfg["hidden_size"], cfg["moe_intermediate_size"],
                   cfg["shared_expert_intermediate_size"], cfg["vocab_size"])
    E = cfg.get("published", {}).get("num_experts", cfg["num_experts"])
    held = cfg["num_experts"]
    H, K, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    m = delta_dims(cfg)
    blocks = _EMBED_BLOCKS if V % _EMBED_BLOCKS == 0 else 1

    def kernel(fan_in, gain=1.0):
        return "kernel", gain * fan_in ** -0.5
    one = ("ln_scale", 0.0)
    out = [("embed", (blocks, V // blocks, h), "embed", 1.0, True),
           # a row of its hidden_size at a time: whole, its float32 draw
           # would be 1.2 GB beside the weights
           ("head", (h, V), *kernel(h), True)]
    for r, (kind, L) in enumerate(runs_of(cfg)):
        p = f"r{r}_"
        out.append((p + "ln1", (L, h), *one, True))
        if kind == "linear_attention":
            out += [
                (p + "w_qkvz", (L, h, m["kh"], m["qkvz"]), *kernel(h), True),
                (p + "w_ba", (L, h, m["kh"], 2 * m["r"]), *kernel(h), True),
                (p + "conv_w", (L, m["taps"], m["conv"]),
                 *kernel(m["taps"]), True),
                (p + "norm", (L, m["dv"]), *one, True),
                (p + "w_out", (L, m["vh"], m["dv"], h),
                 *kernel(m["vh"] * m["dv"]), True)]
        elif kind == "full_attention":
            out += [
                (p + "wq", (L, h, H, 2 * hd), *kernel(h), True),
                (p + "wk", (L, h, K, hd), *kernel(h), True),
                (p + "wv", (L, h, K, hd), *kernel(h), True),
                (p + "qn", (L, hd), *one, True),
                (p + "kn", (L, hd), *one, True),
                (p + "wo", (L, H, hd, h), *kernel(H * hd), True)]
        else:
            raise ValueError(f"unknown layer type {kind!r}")
        out += [
            (p + "ln2", (L, h), *one, True),
            (p + "router", (L, h, E), *kernel(h, ROUTER_GAIN), True),
            (p + "w1", (L, held, h, 2 * f), *kernel(h), True),
            (p + "w2", (L, held, f, h), *kernel(f), True),
            (p + "ws1", (L, h, 2 * fs), *kernel(h), True),
            (p + "ws2", (L, fs, h), *kernel(fs), True),
            (p + "wsg", (L, h, 1), *kernel(h), True)]
    return tuple(out)


@partial(jax.jit, static_argnames=("shape",))
def _decay_constants(key, shape):
    """(A_log, dt_bias) of one run of Gated DeltaNet layers, float32."""
    a = jax.random.uniform(key, shape, jnp.float32, *A_RANGE)
    return jnp.log(a), jnp.ones(shape, jnp.float32)


def make_weights(cfg: dict, seed: int) -> dict:
    """All weights of the configuration from ``seed``, in the served type;
    the decay's constants `A_log` and `dt_bias` in float32, as published
    checkpoints keep them."""
    w = weights.draw(spec(cfg), seed,
                     cfg.get("as_run", {}).get("dtype", "bfloat16"))
    w["embed"] = w["embed"].reshape(-1, cfg["hidden_size"])
    w["norm_f"] = jnp.ones((cfg["hidden_size"],), w["embed"].dtype)
    key = weights.seed_key(seed)
    for r, (kind, n) in enumerate(runs_of(cfg)):
        if kind == "linear_attention":
            w[f"r{r}_A_log"], w[f"r{r}_dt_bias"] = _decay_constants(
                jax.random.fold_in(key, 1000 + r),
                (n, cfg["linear_num_value_heads"]))
        else:
            for k in ("qn", "kn"):
                w[f"r{r}_{k}"] = w[f"r{r}_{k}"] * QK_NORM_SCALE
    return w

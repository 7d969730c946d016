"""granite-4.0-h's tensors under the benchmark's own names: the plain
reference reads them as they are, `program.py` hands the same arrays to the
program's hybrid stack. Layers are stacked a RUN at a time (consecutive
layers of one kind, `r<i>_<name>`), which is how the program scans them, so
that neither side slices a stacked tensor (a slice would be a copy).

Of the routed experts only the share held here is drawn (`num_local_experts`
of the configuration, the first of the router's `published` count): the
router keeps every output."""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from benchmark import weights

# the router's kernel is drawn at twice 1/sqrt(fan-in): its logits then have
# standard deviation 2, the ten largest of 72 lie between about 2.2 and 4.8
# and their softmax between about 0.03 and 0.3, as a trained router's. At 1
# the ten gates are nearly flat (0.06-0.2), and a fault in the gates or in
# which ten were taken would hardly move the logits
ROUTER_GAIN = 2.0
# Mamba-2's published initialisation: A uniform in [1, 16] (A_log its
# logarithm), the step sizes log-uniform in [1e-3, 1e-1] (dt_bias their
# inverse softplus), D ones
A_RANGE = (1.0, 16.0)
DT_RANGE = (1e-3, 1e-1)
# The head is the embedding (tied), and the residual stream still carries
# the input token's embedding times 12 at the last layer: with random
# weights and a final norm scale of ones, every position's largest logit
# would be its own input token's, 45 standard deviations above the rest (12
# |E[t]|^2 against sqrt(4096) |E|), greedy decoding would repeat one token
# and no precision could move the choice: the check would compare nothing.
# A trained model's last hidden state is not aligned with its input
# embedding. The final norm's scale is therefore drawn as random SIGNS
# (+1 / -1): the head then reads the stream through a fixed reflection, the
# own-token term sums to noise, and the logits keep their size.
FINAL_NORM = "signs"


def runs_of(cfg: dict) -> list[tuple[str, int]]:
    """[(kind, layers)] of the consecutive layers of one kind."""
    out: list[list] = []
    for kind in cfg["layer_types"]:
        if out and out[-1][0] == kind:
            out[-1][1] += 1
        else:
            out.append([kind, 1])
    return [(k, n) for k, n in out]


def mamba_dims(cfg: dict) -> dict:
    heads, p, n, g = (cfg["mamba_n_heads"], cfg["mamba_d_head"],
                      cfg["mamba_d_state"], cfg["mamba_n_groups"])
    inner = heads * p
    if inner != cfg["mamba_expand"] * cfg["hidden_size"]:
        raise ValueError("mamba_n_heads x mamba_d_head must be "
                         "mamba_expand x hidden_size")
    return {"heads": heads, "p": p, "n": n, "g": g, "inner": inner,
            "conv": inner + 2 * g * n, "taps": cfg["mamba_d_conv"]}


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def spec(cfg: dict) -> tuple:
    """(name, shape, kind, scale, stacked) of every tensor `weights.draw`
    draws, in order: kernels at 1/sqrt(fan-in), norm scales and D at 1, the
    embedding (which is the head too) at 1/embedding_multiplier, so that
    the residual stream starts at order 1."""
    h, f, fs = (cfg["hidden_size"], cfg["intermediate_size"],
                cfg["shared_intermediate_size"])
    V, E, held = (cfg["vocab_size"],
                  cfg.get("published", {}).get("num_local_experts",
                                               cfg["num_local_experts"]),
                  cfg["num_local_experts"])
    H, K, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                head_dim(cfg))
    m = mamba_dims(cfg)

    def kernel(fan_in, gain=1.0):
        return "kernel", gain * fan_in ** -0.5
    one = ("ln_scale", 0.0)
    out = [("embed", (V, h), "embed", 1.0 / cfg["embedding_multiplier"],
            False)]
    for r, (kind, L) in enumerate(runs_of(cfg)):
        p = f"r{r}_"
        out.append((p + "ln1", (L, h), *one, True))
        if kind == "mamba":
            out += [
                (p + "w_in", (L, h, 2 * m["inner"] + 2 * m["g"] * m["n"]
                              + m["heads"]), *kernel(h), True),
                (p + "conv_w", (L, m["taps"], m["conv"]),
                 *kernel(m["taps"]), True),
                (p + "conv_b", (L, m["conv"]), "kernel", 0.1, True),
                (p + "D", (L, m["heads"]), *one, True),
                (p + "norm", (L, m["inner"]), *one, True),
                (p + "w_out", (L, m["inner"], h), *kernel(m["inner"]),
                 True)]
        elif kind == "attention":
            out += [
                (p + "wq", (L, h, H, hd), *kernel(h), True),
                (p + "wk", (L, h, K, hd), *kernel(h), True),
                (p + "wv", (L, h, K, hd), *kernel(h), True),
                (p + "wo", (L, H, hd, h), *kernel(H * hd), True)]
        else:
            raise ValueError(f"unknown layer type {kind!r}")
        out += [
            (p + "ln2", (L, h), *one, True),
            (p + "router", (L, h, E), *kernel(h, ROUTER_GAIN), True),
            (p + "w1", (L, held, h, 2 * f), *kernel(h), True),
            (p + "w2", (L, held, f, h), *kernel(f), True),
            (p + "ws1", (L, h, 2 * fs), *kernel(h), True),
            (p + "ws2", (L, fs, h), *kernel(fs), True)]
    return tuple(out)


@partial(jax.jit, static_argnames=("shape",))
def _scan_constants(key, shape):
    """(A_log, dt_bias) of one run of mamba layers, float32."""
    ka, kd = jax.random.split(key)
    a = jax.random.uniform(ka, shape, jnp.float32, *A_RANGE)
    dt = jnp.exp(jax.random.uniform(kd, shape, jnp.float32,
                                    math.log(DT_RANGE[0]),
                                    math.log(DT_RANGE[1])))
    return jnp.log(a), dt + jnp.log(-jnp.expm1(-dt))


def make_weights(cfg: dict, seed: int) -> dict:
    """All weights of the configuration from ``seed``, in the served type;
    the scan's constants `A_log` and `dt_bias` in float32, as published
    checkpoints keep them."""
    w = weights.draw(spec(cfg), seed,
                     cfg.get("as_run", {}).get("dtype", "bfloat16"))
    key = weights.seed_key(seed)
    w["norm_f"] = jnp.where(
        jax.random.bernoulli(jax.random.fold_in(key, 999),
                             shape=(cfg["hidden_size"],)), 1.0, -1.0
    ).astype(w["embed"].dtype)
    for r, (kind, n) in enumerate(runs_of(cfg)):
        if kind == "mamba":
            w[f"r{r}_A_log"], w[f"r{r}_dt_bias"] = _scan_constants(
                jax.random.fold_in(key, 1000 + r),
                (n, cfg["mamba_n_heads"]))
    return w

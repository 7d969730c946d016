"""Falcon-H1's tensors under the benchmark's own names: the plain reference
reads them as they are, `program.py` hands the same arrays to the program's
hybrid stack. Every layer is of one kind (both mixers and a dense MLP), so
each tensor is ONE stack over the layers, which is how the program scans
them: neither side slices a stacked tensor (a slice would be a copy).

The published multipliers are muP's: a trained checkpoint's kernels are as
large as it takes for `activation x multiplier` to be of order 1 (its key
projection is some 90 times a plain initialisation's, for a key multiplier
of 0.011). Seeded kernels are therefore drawn at `gain / sqrt(fan-in) /
multiplier`, so that under the published multipliers every activation has
the size it would have without them: keys, gates, step sizes, B and C of
order 1, and the logits of standard deviation 1. Drawn at 1 / sqrt(fan-in)
alone, the scan's input would be 0.06, the gated norm would divide by its
epsilon and neither mixer would move the residual stream.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights

# Mamba-2's published initialisation: A uniform in [1, 16] (A_log its
# logarithm), the step sizes log-uniform in [1e-3, 1e-1] (dt_bias their
# inverse softplus), D ones
A_RANGE = (1.0, 16.0)
DT_RANGE = (1e-3, 1e-1)
# what a branch adds to the residual stream, as the gain of its
# out-projection: three branches a layer at about 0.4 each leave a stream
# that starts at 1 at about 2 after six layers
OUT_GAIN = 0.5
# the queries' gain: scores of standard deviation 3, so that the softmax
# over some thousand keys rests on a few of them, as a trained head's does.
# At 1 it is nearly flat, attention's output is the mean of some thousand
# random values (0.03) and a fault in it would not move the logits
QUERY_GAIN = 3.0


def dims(cfg: dict) -> dict:
    heads, p, n, g = (cfg["mamba_n_heads"], cfg["mamba_d_head"],
                      cfg["mamba_d_state"], cfg["mamba_n_groups"])
    inner = heads * p
    if inner != cfg["mamba_d_ssm"]:
        raise ValueError("mamba_n_heads x mamba_d_head must be mamba_d_ssm")
    return {"heads": heads, "p": p, "n": n, "g": g, "inner": inner,
            "conv": inner + 2 * g * n, "taps": cfg["mamba_d_conv"],
            "proj": 2 * inner + 2 * g * n + heads}


def _embed_blocks(vocab: int) -> int:
    return vocab // 1024 if vocab % 1024 == 0 else 1


def spec(cfg: dict) -> tuple:
    """(name, shape, kind, scale, stacked) of every tensor `weights.draw`
    draws, in order (the module's docstring says why each scale)."""
    h, f, V, L = (cfg["hidden_size"], cfg["intermediate_size"],
                  cfg["vocab_size"], cfg["num_hidden_layers"])
    H, K, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    m = dims(cfg)
    gate_mult, down_mult = cfg["mlp_multipliers"]

    def kernel(fan_in, gain=1.0):
        return "kernel", gain * fan_in ** -0.5
    one = ("ln_scale", 0.0)
    att_in = cfg["attention_in_multiplier"]
    return (
        # both drawn a slice at a time (whole, the float32 draw of either
        # would be 5.3 GB beside the weights): the embedding in blocks of
        # rows (`make_weights` folds them), the head a row of its 5120
        ("embed", (_embed_blocks(V), V // _embed_blocks(V), h), "embed",
         1.0 / cfg["embedding_multiplier"], True),
        ("head", (h, V), *kernel(h, 1.0 / cfg["lm_head_multiplier"]), True),
        ("ln1", (L, h), *one, True),
        # its segments are rescaled after the draw (`_segments`)
        ("w_in", (L, h, m["proj"]), *kernel(h), True),
        ("conv_w", (L, m["taps"], m["conv"]), *kernel(m["taps"]), True),
        ("conv_b", (L, m["conv"]), "kernel", 0.1, True),
        ("D", (L, m["heads"]), *one, True),
        ("norm", (L, m["inner"]), *one, True),
        ("w_out", (L, m["inner"], h),
         *kernel(m["inner"], OUT_GAIN / cfg["ssm_out_multiplier"]), True),
        ("wq", (L, h, H, hd), *kernel(h, QUERY_GAIN / att_in), True),
        ("wk", (L, h, K, hd),
         *kernel(h, 1.0 / (att_in * cfg["key_multiplier"])), True),
        ("wv", (L, h, K, hd), *kernel(h, 1.0 / att_in), True),
        ("wo", (L, H, hd, h),
         *kernel(H * hd, OUT_GAIN / cfg["attention_out_multiplier"]), True),
        ("ln2", (L, h), *one, True),
        ("wg", (L, h, f), *kernel(h, 1.0 / gate_mult), True),
        ("wu", (L, h, f), *kernel(h), True),
        ("wd", (L, f, h), *kernel(f, OUT_GAIN / down_mult), True),
    )


def _fold(embed):
    """[blocks, rows, hidden] -> [vocab, hidden]."""
    return embed.reshape(-1, embed.shape[-1])


def _segments(w_in, scale):
    """The in-projection's columns times ``scale`` [columns]: each segment
    at 1 / (ssm_in_multiplier x its own multiplier)."""
    return (w_in.astype(jnp.float32) * scale).astype(w_in.dtype)


@partial(jax.jit, static_argnames=("shape",))
def _scan_constants(key, shape):
    """(A_log, dt_bias) of the stacked layers, float32."""
    ka, kd = jax.random.split(key)
    a = jax.random.uniform(ka, shape, jnp.float32, *A_RANGE)
    dt = jnp.exp(jax.random.uniform(kd, shape, jnp.float32,
                                    math.log(DT_RANGE[0]),
                                    math.log(DT_RANGE[1])))
    return jnp.log(a), dt + jnp.log(-jnp.expm1(-dt))


def make_weights(cfg: dict, seed: int) -> dict:
    """All weights of the configuration from ``seed``, in the served type;
    the scan's constants `A_log` and `dt_bias` in float32, as published
    checkpoints keep them; the final norm's scale ones (the head is
    untied: no logit is tied to its own input token)."""
    w = weights.draw(spec(cfg), seed,
                     cfg.get("as_run", {}).get("dtype", "bfloat16"))
    m = dims(cfg)
    # on the chip over the donated argument (where it lies: no second copy
    # of a 2.7 GB tensor); the CPU backend of a rehearsal has no donation
    # and would warn
    donate = (0,) if jax.default_backend() == "tpu" else ()
    fold, segments = (jax.jit(f, donate_argnums=donate)
                      for f in (_fold, _segments))
    w["embed"] = fold(w["embed"])
    per = 1.0 / (cfg["ssm_in_multiplier"]
                 * np.asarray(cfg["ssm_multipliers"], np.float32))
    w["w_in"] = segments(w["w_in"], jnp.asarray(np.repeat(per, (
        m["inner"], m["inner"], m["g"] * m["n"], m["g"] * m["n"],
        m["heads"]))))
    w["norm_f"] = jnp.ones((cfg["hidden_size"],), w["embed"].dtype)
    w["A_log"], w["dt_bias"] = _scan_constants(
        jax.random.fold_in(weights.seed_key(seed), 1000),
        (cfg["num_hidden_layers"], cfg["mamba_n_heads"]))
    return w

"""MiniCPM-SALA's tensors under the benchmark's own names: the plain
reference reads them as they are, `program.py` hands the same arrays to the
program's hybrid stack. Layers are stacked a RUN at a time (consecutive
layers of one kind, `r<i>_<name>`), which is how the program scans them, so
that neither side slices a stacked tensor (a slice would be a copy)."""
from __future__ import annotations

from benchmark import weights

# the sparse layers' q/k RMSNorm scales are 2 and not 1: with scales of 1
# and random projections every attention logit has variance 1, a softmax
# over six thousand keys is nearly flat and the attention's output nearly
# nothing, so that a fault in the block selection could not be seen in the
# logits; at 2 x 2 the logits have standard deviation 4 and a few keys
# carry each head, as in a trained model
NORM_GAIN = {"qn": 2.0, "kn": 2.0}


def runs_of(cfg: dict) -> list[tuple[str, int]]:
    """[(kind, layers)] of the consecutive layers of one kind."""
    out: list[list] = []
    for kind in cfg["mixer_types"]:
        if out and out[-1][0] == kind:
            out[-1][1] += 1
        else:
            out.append([kind, 1])
    return [(k, n) for k, n in out]


def spec(cfg: dict) -> tuple:
    """(name, shape, kind, scale, stacked) of every tensor, in the order
    they are drawn: kernels at 1/sqrt(fan-in), norm scales at 1. The
    embedding is drawn at 1/scale_emb and the head at (hidden /
    dim_model_base)/sqrt(hidden), the sizes at which the muP multipliers
    leave a residual stream and logits of order 1, as a trained model's."""
    h, f, V = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    H, K, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    LH, LK, lhd = (cfg["lightning_nh"], cfg["lightning_nkv"],
                   cfg["lightning_head_dim"])

    def kernel(fan_in):
        return "kernel", fan_in ** -0.5
    one = ("ln_scale", 0.0)
    out = [("embed", (V, h), "embed", 1.0 / cfg["scale_emb"], False)]
    for r, (kind, L) in enumerate(runs_of(cfg)):
        if kind == "lightning-attn":
            q, kv, d = LH, LK, lhd
        elif kind == "minicpm4":
            q, kv, d = H, K, hd
        else:
            raise ValueError(f"unknown mixer {kind!r}")
        p = f"r{r}_"
        out += [
            (p + "ln1", (L, h), *one, True),
            (p + "wq", (L, h, q, d), *kernel(h), True),
            (p + "wk", (L, h, kv, d), *kernel(h), True),
            (p + "wv", (L, h, kv, d), *kernel(h), True),
            (p + "qn", (L, d), *one, True),
            (p + "kn", (L, d), *one, True),
            (p + "wz", (L, h, q, d), *kernel(h), True),
            (p + "wo", (L, q, d, h), *kernel(q * d), True),
            (p + "ln2", (L, h), *one, True),
            (p + "wg", (L, h, f), *kernel(h), True),
            (p + "wu", (L, h, f), *kernel(h), True),
            (p + "wd", (L, f, h), *kernel(f), True),
        ]
        if kind == "lightning-attn":
            out.append((p + "on", (L, q, d), *one, True))
    out += [("norm_f", (h,), *one, False),
            ("w_head", (h, V), "kernel",
             (h / cfg["dim_model_base"]) * h ** -0.5, False)]
    return tuple(out)


def make_weights(cfg: dict, seed: int) -> dict:
    """All weights of the configuration from ``seed``, in the served type.
    The decay slopes are computed from the layer's published index, not
    drawn (`reference.slopes`, the program's `models/hybrid.py`)."""
    w = weights.draw(spec(cfg), seed,
                     cfg.get("as_run", {}).get("dtype", "bfloat16"))
    for r, (kind, _n) in enumerate(runs_of(cfg)):
        if kind == "minicpm4":
            for name, gain in NORM_GAIN.items():
                w[f"r{r}_{name}"] = w[f"r{r}_{name}"] * gain
    return w

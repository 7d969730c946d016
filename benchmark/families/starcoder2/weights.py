"""StarCoder2's tensors under the benchmark's own names: the plain
reference reads them as they are, `program.py` lays the same arrays out as
the program's scanned decode pool holds them."""
from __future__ import annotations

from benchmark import weights


def spec(cfg: dict) -> tuple:
    """(name, shape, kind, scale, stacked) of every tensor, in the order
    they are drawn: kernels at 1/sqrt(fan-in), biases and LayerNorm offsets
    at 0.02, LayerNorm scales at 1 +- 0.1, the embedding at 1 - so that no
    term of the block is a no-op that a faulty path could drop unseen."""
    h, H, K = (cfg["hidden_size"], cfg["num_attention_heads"],
               cfg["num_key_value_heads"])
    hd = cfg.get("head_dim") or h // H
    L, f, V = (cfg["num_hidden_layers"], cfg["intermediate_size"],
               cfg["vocab_size"])

    def kernel(fan_in):
        return "kernel", fan_in ** -0.5
    ln_s, bias = ("ln_scale", 0.1), ("bias", 0.02)
    return (
        ("embed", (V, h), "embed", 1.0, False),
        ("ln1_s", (L, h), *ln_s, True), ("ln1_b", (L, h), *bias, True),
        ("wq", (L, h, H, hd), *kernel(h), True), ("bq", (L, H, hd), *bias, True),
        ("wk", (L, h, K, hd), *kernel(h), True), ("bk", (L, K, hd), *bias, True),
        ("wv", (L, h, K, hd), *kernel(h), True), ("bv", (L, K, hd), *bias, True),
        ("wo", (L, H, hd, h), *kernel(H * hd), True), ("bo", (L, h), *bias, True),
        ("ln2_s", (L, h), *ln_s, True), ("ln2_b", (L, h), *bias, True),
        ("w_up", (L, h, f), *kernel(h), True), ("b_up", (L, f), *bias, True),
        ("w_down", (L, f, h), *kernel(f), True), ("b_down", (L, h), *bias, True),
        ("lnf_s", (h,), *ln_s, False), ("lnf_b", (h,), *bias, False),
        ("w_head", (h, V), *kernel(h), False), ("b_head", (V,), *bias, False),
    )


def make_weights(cfg: dict, seed: int) -> dict:
    """All weights of the configuration from ``seed``, in the served type."""
    return weights.draw(spec(cfg), seed,
                        cfg.get("as_run", {}).get("dtype", "bfloat16"))

"""Operations and bytes the algorithm needs, from a configuration's shapes.

These count the work of the published architecture (pre-LayerNorm block with
biases, grouped-query attention, MLP at `intermediate_size`), whatever
implements it: a later kernel cannot make them stale. One multiply-add is
two operations. Nothing here imports the program.
"""
from __future__ import annotations

_DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def dims(cfg: dict) -> dict:
    h = cfg["hidden_size"]
    heads = cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or h // heads
    return {"h": h, "heads": heads, "kvh": cfg["num_key_value_heads"],
            "hd": hd, "ffn": cfg["intermediate_size"],
            "layers": cfg["num_hidden_layers"], "vocab": cfg["vocab_size"],
            "wbytes": _DTYPE_BYTES[cfg.get("as_run", {}).get(
                "dtype", cfg.get("torch_dtype", "bfloat16"))]}


def params_per_layer(cfg: dict, biases: bool = True) -> int:
    d = dims(cfg)
    h, q, kv, f = d["h"], d["heads"] * d["hd"], d["kvh"] * d["hd"], d["ffn"]
    n = h * q + 2 * h * kv + q * h + 2 * h * f          # q, k, v, out, mlp
    if biases:
        n += q + 2 * kv + h + f + h + 4 * h            # + two LayerNorms
    return n


def params_total(cfg: dict, biases: bool = True) -> int:
    """Parameters as run: embedding, layers, final norm and an output head
    (counted apart from the embedding unless the run ties them)."""
    d = dims(cfg)
    tied = cfg.get("as_run", {}).get("tie_word_embeddings",
                                     cfg.get("tie_word_embeddings", False))
    n = d["vocab"] * d["h"] + d["layers"] * params_per_layer(cfg, biases)
    if biases:
        n += 2 * d["h"]
    if not tied:
        n += d["vocab"] * d["h"] + (d["vocab"] if biases else 0)
    return n


def matmul_params(cfg: dict) -> int:
    """Parameters every token multiplies: the layers' kernels and the output
    head (the embedding is a lookup)."""
    d = dims(cfg)
    return d["layers"] * params_per_layer(cfg, biases=False) + d["vocab"] * d["h"]


def kv_bytes_per_token(cfg: dict) -> int:
    d = dims(cfg)
    return 2 * d["kvh"] * d["hd"] * d["layers"] * d["wbytes"]


def weight_bytes(cfg: dict) -> int:
    return params_total(cfg) * dims(cfg)["wbytes"]


def attn_flops(cfg: dict, q_tokens: int, ctx_tokens: float) -> float:
    """QK^T and PV for ``q_tokens`` queries that each attend ``ctx_tokens``
    keys on average, over all layers."""
    d = dims(cfg)
    return 4.0 * d["layers"] * d["heads"] * d["hd"] * q_tokens * ctx_tokens


def forward_flops(cfg: dict, tokens: int, ctx_tokens: float,
                  logits_for: int | None = None) -> float:
    """A forward pass over ``tokens`` positions whose queries attend
    ``ctx_tokens`` keys on average; the head runs on ``logits_for`` of them
    (all, unless given: serving needs the last position's only)."""
    d = dims(cfg)
    body = 2.0 * d["layers"] * params_per_layer(cfg, biases=False) * tokens
    head = 2.0 * d["vocab"] * d["h"] * (tokens if logits_for is None
                                        else logits_for)
    return body + head + attn_flops(cfg, tokens, ctx_tokens)


def prefill_work(cfg: dict, new_tokens: int, cached_tokens: int = 0
                 ) -> tuple[float, float]:
    """(operations, bytes) of prefilling ``new_tokens`` after
    ``cached_tokens`` served from cache: causal attention (each query sees
    the cache and half of the new tokens on average), one position's logits;
    bytes are the weights once, the cached KV read once and the new KV
    written once."""
    ctx = cached_tokens + (new_tokens + 1) / 2.0
    flops = forward_flops(cfg, new_tokens, ctx, logits_for=1)
    kvb = kv_bytes_per_token(cfg)
    nbytes = (matmul_params(cfg) * dims(cfg)["wbytes"]
              + kvb * (cached_tokens + new_tokens))
    return flops, nbytes


def decode_step_work(cfg: dict, context_lengths: list[int] | tuple
                     ) -> tuple[float, float]:
    """(operations, bytes) of ONE decode step for rows whose contexts hold
    ``context_lengths`` tokens: every row multiplies every kernel, attends
    its own context; bytes are the weights once, each row's KV read once and
    one token's KV written per row, and the rows' activations."""
    d = dims(cfg)
    rows = len(context_lengths)
    ctx = float(sum(context_lengths))
    flops = (2.0 * matmul_params(cfg) * rows
             + 4.0 * d["layers"] * d["heads"] * d["hd"] * ctx)
    nbytes = (matmul_params(cfg) * d["wbytes"]
              + kv_bytes_per_token(cfg) * (ctx + rows)
              + rows * d["wbytes"] * (2 * d["layers"] * (3 * d["h"] + d["ffn"])
                                     + d["vocab"]))
    return flops, nbytes

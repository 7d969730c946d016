"""Operations and bytes the algorithm needs, from a configuration's shapes.

These count the work of the published architecture (a stack of `minicpm4`
block-sparse layers and `lightning-attn` linear layers, gated SiLU MLP, no
biases), whatever implements it: a later kernel cannot make them stale. One
multiply-add is two operations. Nothing here imports the program.

What a token and a slot hold takes the place of StarCoder2's
`kv_bytes_per_token`: only the sparse layers keep keys and values a token
(`kv_bytes_per_token`), with their pooled keys (`pooled_bytes_per_token`);
a lightning layer keeps one float32 state a slot (`state_bytes_per_slot`).
"""
from __future__ import annotations

import numpy as np

_DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}
SPARSE, LINEAR = "minicpm4", "lightning-attn"


def dims(cfg: dict) -> dict:
    sp = cfg["sparse_config"]
    kinds = cfg["mixer_types"]
    return {"h": cfg["hidden_size"], "ffn": cfg["intermediate_size"],
            "vocab": cfg["vocab_size"],
            "heads": cfg["num_attention_heads"],
            "kvh": cfg["num_key_value_heads"], "hd": cfg["head_dim"],
            "lh": cfg["lightning_nh"], "lkv": cfg["lightning_nkv"],
            "lhd": cfg["lightning_head_dim"],
            "n_sparse": sum(1 for k in kinds if k == SPARSE),
            "n_linear": sum(1 for k in kinds if k == LINEAR),
            "sp": sp,
            "wbytes": _DTYPE_BYTES[cfg.get("as_run", {}).get(
                "dtype", cfg.get("torch_dtype", "bfloat16"))]}


def params_per_layer(cfg: dict, kind: str) -> int:
    """Matrix parameters of one layer: q, k, v, output gate, out, and the
    three matrices of the gated MLP (norm scales are not counted: 4096 a
    norm)."""
    d = dims(cfg)
    h, f = d["h"], d["ffn"]
    if kind == SPARSE:
        q, kv = d["heads"] * d["hd"], d["kvh"] * d["hd"]
    elif kind == LINEAR:
        q, kv = d["lh"] * d["lhd"], d["lkv"] * d["lhd"]
    else:
        raise ValueError(f"unknown mixer {kind!r}")
    return h * q + 2 * h * kv + h * q + q * h + 3 * h * f


def matmul_params(cfg: dict) -> int:
    """Parameters every token multiplies: the layers' kernels and the output
    head (the embedding is a lookup)."""
    d = dims(cfg)
    return (d["n_sparse"] * params_per_layer(cfg, SPARSE)
            + d["n_linear"] * params_per_layer(cfg, LINEAR)
            + d["vocab"] * d["h"])


def params_total(cfg: dict) -> int:
    """Parameters as run: the untied embedding and head, the layers'
    kernels (the norm scales, some tens of thousands, are left out)."""
    d = dims(cfg)
    return matmul_params(cfg) + d["vocab"] * d["h"]


def weight_bytes(cfg: dict) -> int:
    return params_total(cfg) * dims(cfg)["wbytes"]


def kv_bytes_per_token(cfg: dict) -> int:
    """Keys and values a token holds: the sparse layers' alone."""
    d = dims(cfg)
    return 2 * d["kvh"] * d["hd"] * d["n_sparse"] * d["wbytes"]


def pooled_bytes_per_token(cfg: dict) -> float:
    """The indexer's cache a token: one pooled key every `kernel_stride`."""
    d = dims(cfg)
    return (d["kvh"] * d["hd"] * d["n_sparse"] * d["wbytes"]
            / d["sp"]["kernel_stride"])


def state_bytes_per_slot(cfg: dict, layers: int | None = None) -> int:
    """Recurrent state a slot holds (float32), over ``layers`` lightning
    layers (all of them unless given)."""
    d = dims(cfg)
    n = d["n_linear"] if layers is None else layers
    return 4 * n * d["lh"] * d["lhd"] * d["lhd"]


def attended(cfg: dict, context):
    """Tokens a sparse layer's query attends at a context of ``context``
    tokens (a number or an array of them): all of them up to `dense_len`,
    else block 0, the window and `topk` blocks."""
    sp = dims(cfg)["sp"]
    cap = (sp["window_size"] + sp["topk"] * sp["block_size"]
           + sp["init_blocks"] * sp["block_size"])
    context = np.asarray(context)
    return np.where(context <= sp["dense_len"], context,
                    np.minimum(context, cap))


def prefill_work(cfg: dict, new_tokens: int, cached_tokens: int = 0
                 ) -> tuple[float, float]:
    """(operations, bytes) of prefilling ``new_tokens`` after
    ``cached_tokens``: every token multiplies every kernel; a sparse
    layer's causal query attends what `attended` says of its own context
    (summed over the new tokens); one position's logits. Bytes: the
    weights once, the sparse layers' K/V written once, one state a
    lightning layer written once."""
    d = dims(cfg)
    body = 2.0 * (matmul_params(cfg) - d["vocab"] * d["h"]) * new_tokens
    head = 2.0 * d["vocab"] * d["h"]
    att = float(attended(cfg, np.arange(
        cached_tokens + 1, cached_tokens + new_tokens + 1)).sum())
    flops = (body + head
             + d["n_sparse"] * 4.0 * d["heads"] * d["hd"] * att
             + new_tokens * d["n_linear"] * 4.0 * d["lh"] * d["lhd"]
             * d["lhd"])
    nbytes = (matmul_params(cfg) * d["wbytes"]
              + kv_bytes_per_token(cfg) * (cached_tokens + new_tokens)
              + state_bytes_per_slot(cfg))
    return flops, nbytes


def decode_step_work(cfg: dict, context_lengths: list | tuple
                     ) -> tuple[float, float]:
    """(operations, bytes) of ONE decode step for rows whose contexts hold
    ``context_lengths`` tokens. Every row multiplies every kernel. Bytes:
    the weights once; a live row and lightning layer, the state read and
    written once; a live row and sparse layer, the pooled keys of its
    context and K and V of the tokens it attends, one token's K/V written;
    the rows' activations."""
    d = dims(cfg)
    rows = len(context_lengths)
    att = float(attended(cfg, context_lengths).sum())
    ctx = float(sum(context_lengths))
    flops = (2.0 * matmul_params(cfg) * rows
             + d["n_sparse"] * 4.0 * d["heads"] * d["hd"] * att
             + d["n_sparse"] * 2.0 * d["heads"] * d["hd"] * ctx
             / d["sp"]["kernel_stride"]
             + rows * d["n_linear"] * 4.0 * d["lh"] * d["lhd"] * d["lhd"])
    layers = d["n_sparse"] + d["n_linear"]
    nbytes = (matmul_params(cfg) * d["wbytes"]
              + rows * 2 * state_bytes_per_slot(cfg)
              + kv_bytes_per_token(cfg) * (att + rows)
              + pooled_bytes_per_token(cfg) * ctx
              + rows * d["wbytes"] * (2 * layers * (3 * d["h"] + d["ffn"])
                                     + d["vocab"]))
    return flops, nbytes

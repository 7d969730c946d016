"""Operations and bytes the algorithm needs, from a configuration's shapes.

These count the work of the published architecture (`falcon_h1`: every layer
an attention mixer AND a Mamba-2 mixer on one normed input, then a dense
gated MLP; an untied head), whatever implements it: a later kernel cannot
make them stale. BOTH mixers, the MLP and the head are counted in a decode
step and in a prefill; one multiply-add is two operations. Nothing here
imports the program.

What a slot holds: every layer keeps keys and values a token
(`kv_bytes_per_token`) AND one float32 scan state [heads, d_head, d_state]
with a window of `d_conv - 1` convolution inputs (`state_bytes_per_slot`,
`window_bytes_per_slot`).
"""
from __future__ import annotations

_DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def dims(cfg: dict) -> dict:
    heads, p, n, g = (cfg["mamba_n_heads"], cfg["mamba_d_head"],
                      cfg["mamba_d_state"], cfg["mamba_n_groups"])
    inner = heads * p
    return {"h": cfg["hidden_size"], "f": cfg["intermediate_size"],
            "vocab": cfg["vocab_size"],
            "heads": cfg["num_attention_heads"],
            "kvh": cfg["num_key_value_heads"], "hd": cfg["head_dim"],
            "mh": heads, "mp": p, "mn": n, "inner": inner,
            "conv": inner + 2 * g * n, "taps": cfg["mamba_d_conv"],
            "proj": 2 * inner + 2 * g * n + heads,
            "chunk": cfg["mamba_chunk_size"],
            "layers": cfg["num_hidden_layers"],
            "wbytes": _DTYPE_BYTES[cfg.get("as_run", {}).get(
                "dtype", "bfloat16")]}


def attention_params(cfg: dict) -> int:
    """q, k, v and o of one layer."""
    d = dims(cfg)
    q, kv = d["heads"] * d["hd"], d["kvh"] * d["hd"]
    return 2 * d["h"] * q + 2 * d["h"] * kv


def ssm_params(cfg: dict) -> int:
    """One layer's state-space mixer: the in- and out-projection, the
    convolution's taps and bias (A, D, dt_bias and the norm are some
    thousands and are left out)."""
    d = dims(cfg)
    return (d["h"] * d["proj"] + d["inner"] * d["h"]
            + (d["taps"] + 1) * d["conv"])


def mlp_params(cfg: dict) -> int:
    d = dims(cfg)
    return 3 * d["h"] * d["f"]


def layer_params(cfg: dict) -> int:
    return attention_params(cfg) + ssm_params(cfg) + mlp_params(cfg)


def head_params(cfg: dict) -> int:
    d = dims(cfg)
    return d["vocab"] * d["h"]


def token_params(cfg: dict) -> int:
    """Parameters ONE token multiplies: every layer's two mixers and MLP,
    and the head (the embedding is a lookup)."""
    return dims(cfg)["layers"] * layer_params(cfg) + head_params(cfg)


def params_total(cfg: dict) -> int:
    """Parameters as held: the layers, the embedding and the untied head."""
    return token_params(cfg) + head_params(cfg)


def weight_bytes(cfg: dict) -> int:
    return params_total(cfg) * dims(cfg)["wbytes"]


def streamed_weight_bytes(cfg: dict) -> int:
    """Weight bytes a step has to read whatever its tokens: the layers and
    the head once (of the embedding only its tokens' rows)."""
    return token_params(cfg) * dims(cfg)["wbytes"]


def kv_bytes_per_token(cfg: dict) -> int:
    """Keys and values a token holds, over all layers."""
    d = dims(cfg)
    return 2 * d["kvh"] * d["hd"] * d["layers"] * d["wbytes"]


def state_bytes_per_slot(cfg: dict) -> int:
    """The float32 scan states a slot holds, over all layers."""
    d = dims(cfg)
    return 4 * d["layers"] * d["mh"] * d["mp"] * d["mn"]


def window_bytes_per_slot(cfg: dict) -> int:
    """The convolution windows a slot holds (`d_conv - 1` inputs a layer)."""
    d = dims(cfg)
    return d["layers"] * (d["taps"] - 1) * d["conv"] * d["wbytes"]


def scan_flops_per_token(cfg: dict, chunked: bool) -> float:
    """One layer's scan, a token: the state's update and its read-out (2 x
    2 x heads x P x N); in a chunked prefill the products inside a chunk
    besides (a token against the `chunk / 2` before it in its chunk on
    average: C.B over N, then the heads' P values). The convolution's
    multiply-adds are among `ssm_params`."""
    d = dims(cfg)
    flops = 4.0 * d["mh"] * d["mp"] * d["mn"]
    if chunked:
        flops += d["chunk"] / 2 * (2.0 * d["mn"] + 2.0 * d["mh"] * d["mp"])
    return flops


def cache_bytes(cfg: dict, context_lengths) -> float:
    """Of `decode_step_work`'s bytes, the two mixers' caches: a live row's
    K and V of its context read and one token's written, its scan states
    and windows read and written once."""
    rows = len(context_lengths)
    ctx = float(sum(context_lengths))
    return (kv_bytes_per_token(cfg) * (ctx + rows)
            + rows * 2 * (state_bytes_per_slot(cfg)
                          + window_bytes_per_slot(cfg)))


def prefill_work(cfg: dict, new_tokens: int, cached_tokens: int = 0
                 ) -> tuple[float, float]:
    """(operations, bytes) of prefilling ``new_tokens`` after
    ``cached_tokens``: every token multiplies both mixers and the MLP of
    every layer (the head for one position only), runs the scans, and its
    causal query attends its own context. Bytes: the weights once, K/V
    written once, one state and window a layer written once."""
    d = dims(cfg)
    head = head_params(cfg)
    att = new_tokens * cached_tokens + new_tokens * (new_tokens + 1) / 2
    flops = (2.0 * (token_params(cfg) - head) * new_tokens + 2.0 * head
             + d["layers"] * (scan_flops_per_token(cfg, True) * new_tokens
                              + 4.0 * d["heads"] * d["hd"] * att))
    nbytes = (streamed_weight_bytes(cfg)
              + kv_bytes_per_token(cfg) * (cached_tokens + new_tokens)
              + state_bytes_per_slot(cfg) + window_bytes_per_slot(cfg))
    return flops, nbytes


def decode_step_work(cfg: dict, context_lengths: list | tuple
                     ) -> tuple[float, float]:
    """(operations, bytes) of ONE decode step for rows whose contexts hold
    ``context_lengths`` tokens. Bytes: the layers' weights and the head
    once; the live rows' caches (`cache_bytes`); the rows' activations."""
    d = dims(cfg)
    rows = len(context_lengths)
    ctx = float(sum(context_lengths))
    flops = (2.0 * token_params(cfg) * rows
             + d["layers"] * (scan_flops_per_token(cfg, False) * rows
                              + 4.0 * d["heads"] * d["hd"] * ctx))
    acts = (d["layers"] * (2 * d["proj"]
                           + 2 * (d["heads"] + 2 * d["kvh"]) * d["hd"]
                           + 4 * d["h"] + 3 * d["f"])
            + d["vocab"])
    nbytes = (streamed_weight_bytes(cfg) + cache_bytes(cfg, context_lengths)
              + rows * d["wbytes"] * acts)
    return flops, nbytes

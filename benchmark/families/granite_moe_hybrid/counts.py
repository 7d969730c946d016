"""Operations and bytes the algorithm needs, from a configuration's shapes.

These count the work of the published architecture (`granitemoehybrid`: a
stack of Mamba-2 and plain attention layers, each followed by routed experts
and a shared expert) as ROUTED, whatever implements it: a token multiplies
its mixer, the shared expert, the router and the experts it picked among
those HELD here (`num_local_experts` of the router's published count:
`experts_per_token x held / all` of them on average), never every held
expert. A later kernel cannot make them stale. One multiply-add is two
operations. Nothing here imports the program.

What a slot holds beside keys and values: a Mamba-2 layer keeps one float32
scan state [heads, d_head, d_state] and a window of `d_conv - 1` convolution
inputs (`state_bytes_per_slot`, `window_bytes_per_slot`); only the attention
layers keep keys and values a token (`kv_bytes_per_token`).
"""
from __future__ import annotations

_DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}
MAMBA, ATTENTION = "mamba", "attention"


def dims(cfg: dict) -> dict:
    kinds = cfg["layer_types"]
    heads, p, n, g = (cfg["mamba_n_heads"], cfg["mamba_d_head"],
                      cfg["mamba_d_state"], cfg["mamba_n_groups"])
    return {"h": cfg["hidden_size"], "f": cfg["intermediate_size"],
            "fs": cfg["shared_intermediate_size"],
            "vocab": cfg["vocab_size"],
            "heads": cfg["num_attention_heads"],
            "kvh": cfg["num_key_value_heads"],
            "hd": cfg["hidden_size"] // cfg["num_attention_heads"],
            "mh": heads, "mp": p, "mn": n, "inner": heads * p,
            "conv": heads * p + 2 * g * n, "taps": cfg["mamba_d_conv"],
            "chunk": cfg["mamba_chunk_size"],
            "experts": cfg.get("published", {}).get(
                "num_local_experts", cfg["num_local_experts"]),
            "held": cfg["num_local_experts"],
            "top_k": cfg["num_experts_per_tok"],
            "n_mamba": sum(1 for k in kinds if k == MAMBA),
            "n_attn": sum(1 for k in kinds if k == ATTENTION),
            "layers": len(kinds),
            "wbytes": _DTYPE_BYTES[cfg.get("as_run", {}).get(
                "dtype", "bfloat16")]}


def mixer_params(cfg: dict, kind: str) -> int:
    """Matrix parameters of one mixer: a Mamba-2 layer's in- and
    out-projection (its convolution, A, D, dt_bias and norm are some tens
    of thousands and are left out), an attention layer's q, k, v and o."""
    d = dims(cfg)
    if kind == MAMBA:
        return (d["h"] * (d["inner"] + d["conv"] + d["mh"])
                + d["inner"] * d["h"])
    if kind == ATTENTION:
        q, kv = d["heads"] * d["hd"], d["kvh"] * d["hd"]
        return 2 * d["h"] * q + 2 * d["h"] * kv
    raise ValueError(f"unknown layer type {kind!r}")


def expert_params(cfg: dict) -> int:
    """One routed expert: the gated in-projection and the out-projection."""
    d = dims(cfg)
    return 3 * d["h"] * d["f"]


def outside_expert_params(cfg: dict) -> int:
    """What every token of a layer multiplies besides its mixer and its
    picks: the shared expert and the router (over all the experts)."""
    d = dims(cfg)
    return 3 * d["h"] * d["fs"] + d["h"] * d["experts"]


def picks_held(cfg: dict) -> float:
    """Picks of one token that fall on the experts held here, under
    uniform routing."""
    d = dims(cfg)
    return d["top_k"] * d["held"] / d["experts"]


def experts_touched(cfg: dict, tokens: float) -> float:
    """Held experts of one layer that at least one of ``tokens`` tokens
    picks, under uniform routing: each misses a given expert with
    probability 1 - top_k / experts."""
    d = dims(cfg)
    return d["held"] * (1.0 - (1.0 - d["top_k"] / d["experts"]) ** tokens)


def token_params(cfg: dict) -> float:
    """Parameters ONE token multiplies: the mixers, the shared experts and
    routers, its picks among the held experts, the head (the embedding is a
    lookup; tied, so the head is the same tensor)."""
    d = dims(cfg)
    return (d["n_mamba"] * mixer_params(cfg, MAMBA)
            + d["n_attn"] * mixer_params(cfg, ATTENTION)
            + d["layers"] * (outside_expert_params(cfg)
                             + picks_held(cfg) * expert_params(cfg))
            + d["vocab"] * d["h"])


def params_total(cfg: dict) -> int:
    """Parameters as held: every mixer, shared expert, router and held
    expert, and the tied embedding once."""
    d = dims(cfg)
    return (d["n_mamba"] * mixer_params(cfg, MAMBA)
            + d["n_attn"] * mixer_params(cfg, ATTENTION)
            + d["layers"] * (outside_expert_params(cfg)
                             + d["held"] * expert_params(cfg))
            + d["vocab"] * d["h"])


def weight_bytes(cfg: dict) -> int:
    return params_total(cfg) * dims(cfg)["wbytes"]


def streamed_weight_bytes(cfg: dict, tokens: float) -> float:
    """Weight bytes a step over ``tokens`` tokens has to read: everything
    outside the routed experts once, and of each layer's held experts
    those that some token picks."""
    d = dims(cfg)
    fixed = params_total(cfg) - d["layers"] * d["held"] * expert_params(cfg)
    return d["wbytes"] * (fixed + d["layers"] * experts_touched(cfg, tokens)
                          * expert_params(cfg))


def kv_bytes_per_token(cfg: dict) -> int:
    """Keys and values a token holds: the attention layers' alone."""
    d = dims(cfg)
    return 2 * d["kvh"] * d["hd"] * d["n_attn"] * d["wbytes"]


def state_bytes_per_slot(cfg: dict) -> int:
    """The float32 scan state a slot holds over the Mamba-2 layers."""
    d = dims(cfg)
    return 4 * d["n_mamba"] * d["mh"] * d["mp"] * d["mn"]


def window_bytes_per_slot(cfg: dict) -> int:
    """The convolution windows a slot holds (`d_conv - 1` inputs a layer)."""
    d = dims(cfg)
    return d["n_mamba"] * (d["taps"] - 1) * d["conv"] * d["wbytes"]


def scan_flops_per_token(cfg: dict, chunked: bool) -> float:
    """One Mamba-2 layer's scan, a token: the state's update and its
    read-out (2 x 2 x heads x P x N); in a chunked prefill the products
    inside a chunk besides (a token against the `chunk / 2` before it in
    its chunk on average: C.B over N, then the heads' P values), and the
    convolution."""
    d = dims(cfg)
    flops = 4.0 * d["mh"] * d["mp"] * d["mn"] + 2.0 * d["taps"] * d["conv"]
    if chunked:
        flops += d["chunk"] / 2 * (2.0 * d["mn"] + 2.0 * d["mh"] * d["mp"])
    return flops


def prefill_work(cfg: dict, new_tokens: int, cached_tokens: int = 0
                 ) -> tuple[float, float]:
    """(operations, bytes) of prefilling ``new_tokens`` after
    ``cached_tokens``: every token multiplies `token_params` (the head for
    one position only), runs the scans, and its causal query attends its
    own context in the attention layers. Bytes: the weights its tokens
    touch once, the attention layers' K/V written once, one state and
    window a Mamba-2 layer written once."""
    d = dims(cfg)
    head = d["vocab"] * d["h"]
    att = new_tokens * cached_tokens + new_tokens * (new_tokens + 1) / 2
    flops = (2.0 * (token_params(cfg) - head) * new_tokens + 2.0 * head
             + d["n_mamba"] * scan_flops_per_token(cfg, True) * new_tokens
             + d["n_attn"] * 4.0 * d["heads"] * d["hd"] * att)
    nbytes = (streamed_weight_bytes(cfg, new_tokens)
              + kv_bytes_per_token(cfg) * (cached_tokens + new_tokens)
              + state_bytes_per_slot(cfg) + window_bytes_per_slot(cfg))
    return flops, nbytes


def decode_step_work(cfg: dict, context_lengths: list | tuple
                     ) -> tuple[float, float]:
    """(operations, bytes) of ONE decode step for rows whose contexts hold
    ``context_lengths`` tokens. Bytes: the weights outside the experts
    once and the held experts that some row picks; a live row and Mamba-2
    layer, the state and the window read and written once; a live row and
    attention layer, K and V of its context read and one token's written;
    the rows' activations."""
    d = dims(cfg)
    rows = len(context_lengths)
    ctx = float(sum(context_lengths))
    flops = (2.0 * token_params(cfg) * rows
             + d["n_mamba"] * scan_flops_per_token(cfg, False) * rows
             + d["n_attn"] * 4.0 * d["heads"] * d["hd"] * ctx)
    acts = (d["n_mamba"] * 2 * (d["inner"] + d["conv"] + d["mh"])
            + d["n_attn"] * 2 * (d["heads"] + 2 * d["kvh"]) * d["hd"]
            + d["layers"] * (4 * d["h"] + 3 * d["fs"]
                             + picks_held(cfg) * 3 * d["f"])
            + d["vocab"])
    nbytes = (streamed_weight_bytes(cfg, rows)
              + rows * 2 * (state_bytes_per_slot(cfg)
                            + window_bytes_per_slot(cfg))
              + kv_bytes_per_token(cfg) * (ctx + rows)
              + rows * d["wbytes"] * acts)
    return flops, nbytes

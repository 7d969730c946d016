"""Operations and bytes the algorithm needs, from a configuration's shapes.

These count the work of the published architecture (`qwen3_next`: Gated
DeltaNet and gated-attention layers, each followed by routed experts and a
gated shared expert; an untied head) as ROUTED, whatever implements it: a
token multiplies its mixer, the shared expert and its gate, the router and
the experts it picked among those HELD here (`num_experts` of the router's
published count: `experts_per_token x held / all` of them on average),
never every held expert. A later kernel cannot make them stale. One
multiply-add is two operations. Nothing here imports the program.

What a slot holds beside keys and values: a Gated DeltaNet layer keeps one
float32 state [value heads, dk, dv] and a window of `taps - 1` convolution
inputs (`state_bytes_per_slot`, `window_bytes_per_slot`); only the attention
layers keep keys and values a token (`kv_bytes_per_token`). A decode step
reads and writes a live row's states once (`delta_state_step_bytes`).
"""
from __future__ import annotations

_DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}
DELTA, ATTENTION = "linear_attention", "full_attention"


def dims(cfg: dict) -> dict:
    kinds = cfg["layer_types"]
    kh, vh = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    return {"h": cfg["hidden_size"], "f": cfg["moe_intermediate_size"],
            "fs": cfg["shared_expert_intermediate_size"],
            "vocab": cfg["vocab_size"],
            "heads": cfg["num_attention_heads"],
            "kvh": cfg["num_key_value_heads"], "hd": cfg["head_dim"],
            "kh": kh, "vh": vh, "dk": dk, "dv": dv,
            "conv": 2 * kh * dk + vh * dv,
            "taps": cfg["linear_conv_kernel_dim"], "chunk": 64,
            "experts": cfg.get("published", {}).get(
                "num_experts", cfg["num_experts"]),
            "held": cfg["num_experts"],
            "top_k": cfg["num_experts_per_tok"],
            "n_delta": sum(1 for k in kinds if k == DELTA),
            "n_attn": sum(1 for k in kinds if k == ATTENTION),
            "layers": len(kinds),
            "wbytes": _DTYPE_BYTES[cfg.get("as_run", {}).get(
                "dtype", "bfloat16")]}


def mixer_params(cfg: dict, kind: str) -> int:
    """Parameters of one mixer: a Gated DeltaNet layer's in-projections
    (q, k, v, z; b, a), convolution and out-projection (its A_log, dt_bias
    and norm are some hundreds and are left out), a gated-attention
    layer's q with its gate, k, v and o."""
    d = dims(cfg)
    if kind == DELTA:
        return (d["h"] * (2 * d["kh"] * d["dk"] + 2 * d["vh"] * d["dv"]
                          + 2 * d["vh"])
                + d["taps"] * d["conv"] + d["vh"] * d["dv"] * d["h"])
    if kind == ATTENTION:
        q, kv = d["heads"] * d["hd"], d["kvh"] * d["hd"]
        return 3 * d["h"] * q + 2 * d["h"] * kv
    raise ValueError(f"unknown layer type {kind!r}")


def expert_params(cfg: dict) -> int:
    """One routed expert: the gated in-projection and the out-projection."""
    d = dims(cfg)
    return 3 * d["h"] * d["f"]


def outside_expert_params(cfg: dict) -> int:
    """What every token of a layer multiplies besides its mixer and its
    picks: the shared expert, its gate and the router (over all the
    experts)."""
    d = dims(cfg)
    return 3 * d["h"] * d["fs"] + d["h"] + d["h"] * d["experts"]


def head_params(cfg: dict) -> int:
    d = dims(cfg)
    return d["vocab"] * d["h"]


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


def _mixers(cfg: dict) -> int:
    d = dims(cfg)
    return (d["n_delta"] * mixer_params(cfg, DELTA)
            + d["n_attn"] * mixer_params(cfg, ATTENTION))


def token_params(cfg: dict) -> float:
    """Parameters ONE token multiplies: the mixers, the shared experts,
    gates and routers, its picks among the held experts, the head (the
    embedding is a lookup)."""
    d = dims(cfg)
    return (_mixers(cfg) + d["layers"] * (outside_expert_params(cfg)
                                          + picks_held(cfg)
                                          * expert_params(cfg))
            + head_params(cfg))


def params_total(cfg: dict) -> int:
    """Parameters as held: every mixer, shared expert, gate, router and
    held expert, the embedding and the untied head."""
    d = dims(cfg)
    return (_mixers(cfg) + d["layers"] * (outside_expert_params(cfg)
                                          + d["held"] * expert_params(cfg))
            + 2 * head_params(cfg))


def weight_bytes(cfg: dict) -> int:
    return params_total(cfg) * dims(cfg)["wbytes"]


def streamed_weight_bytes(cfg: dict, tokens: float) -> float:
    """Weight bytes a step over ``tokens`` tokens has to read: everything
    outside the routed experts once (the embedding's rows aside: a lookup),
    and of each layer's held experts those that some token picks."""
    d = dims(cfg)
    fixed = (params_total(cfg) - head_params(cfg)
             - d["layers"] * d["held"] * expert_params(cfg))
    return d["wbytes"] * (fixed + d["layers"] * experts_touched(cfg, tokens)
                          * expert_params(cfg))


def kv_bytes_per_token(cfg: dict) -> int:
    """Keys and values a token holds: the attention layers' alone."""
    d = dims(cfg)
    return 2 * d["kvh"] * d["hd"] * d["n_attn"] * d["wbytes"]


def state_bytes_per_slot(cfg: dict) -> int:
    """The float32 delta-rule states a slot holds over the Gated DeltaNet
    layers."""
    d = dims(cfg)
    return 4 * d["n_delta"] * d["vh"] * d["dk"] * d["dv"]


def window_bytes_per_slot(cfg: dict) -> int:
    """The convolution windows a slot holds (`taps - 1` inputs a layer)."""
    d = dims(cfg)
    return d["n_delta"] * (d["taps"] - 1) * d["conv"] * d["wbytes"]


def delta_state_step_bytes(cfg: dict, rows: int) -> int:
    """Bytes of delta-rule state and window a decode step over ``rows``
    live rows must move: each read and written once."""
    return rows * 2 * (state_bytes_per_slot(cfg) + window_bytes_per_slot(cfg))


def delta_flops_per_token(cfg: dict, chunked: bool) -> float:
    """One Gated DeltaNet layer's recurrence and convolution, a token: the
    decay, the read along k, the update and the read-out along q (7 x dk x
    dv a value head); in a prefill's chunked form the products inside a
    chunk instead (a token against the `chunk / 2` before it: K K^T and Q
    K^T over dk, the inverse's rows, W and U, the read-out of the new
    values), and three products of the state [dk, dv] a token."""
    d = dims(cfg)
    dk, dv, c = d["dk"], d["dv"], d["chunk"]
    flops = 2.0 * d["taps"] * d["conv"]
    if chunked:
        flops += d["vh"] * (2.0 * c / 2 * (2 * dk + dv) + c * c / 3
                            + 2.0 * c * (dk + dv) + 6.0 * dk * dv)
    else:
        flops += d["vh"] * 7.0 * dk * dv
    return flops


def prefill_work(cfg: dict, new_tokens: int, cached_tokens: int = 0
                 ) -> tuple[float, float]:
    """(operations, bytes) of prefilling ``new_tokens`` after
    ``cached_tokens``: every token multiplies `token_params` (the head for
    one position only), runs the delta rule, and its causal query attends
    its own context in the attention layers. Bytes: the weights its tokens
    touch once, the attention layers' K/V written once, one state and
    window a Gated DeltaNet layer written once."""
    d = dims(cfg)
    head = head_params(cfg)
    att = new_tokens * cached_tokens + new_tokens * (new_tokens + 1) / 2
    flops = (2.0 * (token_params(cfg) - head) * new_tokens + 2.0 * head
             + d["n_delta"] * delta_flops_per_token(cfg, True) * new_tokens
             + d["n_attn"] * 4.0 * d["heads"] * d["hd"] * att)
    nbytes = (streamed_weight_bytes(cfg, new_tokens)
              + kv_bytes_per_token(cfg) * (cached_tokens + new_tokens)
              + state_bytes_per_slot(cfg) + window_bytes_per_slot(cfg))
    return flops, nbytes


def cache_bytes(cfg: dict, context_lengths) -> float:
    """Of `decode_step_work`'s bytes, the mixers' caches: a live row's K
    and V of its context read and one token's written, its delta-rule
    states and windows read and written once."""
    rows = len(context_lengths)
    return (kv_bytes_per_token(cfg) * (float(sum(context_lengths)) + rows)
            + delta_state_step_bytes(cfg, rows))


def decode_step_work(cfg: dict, context_lengths: list | tuple
                     ) -> tuple[float, float]:
    """(operations, bytes) of ONE decode step for rows whose contexts hold
    ``context_lengths`` tokens. Bytes: the weights outside the experts
    once and the held experts that some row picks; the caches
    (`cache_bytes`); the rows' activations."""
    d = dims(cfg)
    rows = len(context_lengths)
    ctx = float(sum(context_lengths))
    flops = (2.0 * token_params(cfg) * rows
             + d["n_delta"] * delta_flops_per_token(cfg, False) * rows
             + d["n_attn"] * 4.0 * d["heads"] * d["hd"] * ctx)
    acts = (d["n_delta"] * 2 * (2 * d["conv"] + d["vh"] * d["dv"])
            + d["n_attn"] * 2 * (3 * d["heads"] + 2 * d["kvh"]) * d["hd"]
            + d["layers"] * (4 * d["h"] + 3 * d["fs"]
                             + picks_held(cfg) * 3 * d["f"])
            + d["vocab"])
    nbytes = (streamed_weight_bytes(cfg, rows) + cache_bytes(
        cfg, context_lengths) + rows * d["wbytes"] * acts)
    return flops, nbytes

"""Qwen3-Next as the program runs it (`idunno_tpu/models/hybrid.py`: the
`gated-deltanet` kind, the `attention` kind with its q/k norm, partial
rotary positions and output gate, and the `moe` feed-forward with a gated
shared expert): the only file of the family that imports the program."""
from __future__ import annotations

import jax.numpy as jnp

# at the top, not inside `build`: a program whose hybrid stack has no
# gated-deltanet kind (the parent of the PR that brought this family) then
# fails as the family is loaded, within seconds, and not after the weights
# are drawn
from idunno_tpu.models.hybrid import ATTENTION, DELTA, MOE, HybridLM

_KIND = {"linear_attention": DELTA, "full_attention": ATTENTION}
_LAYER = ("ln1", "w_qkvz", "w_ba", "conv_w", "dt_bias", "A_log", "norm",
          "w_out", "wq", "wk", "wv", "qn", "kn", "wo", "ln2", "router", "w1",
          "w2", "ws1", "ws2", "wsg")


def derive(cfg: dict) -> dict:
    """What follows from a configuration's sizes where a rehearsal has
    swapped them: nothing is computed, the rehearsal states every size."""
    return dict(cfg)


def program_params(w: dict) -> dict:
    """The same arrays under the program's names: a run of consecutive
    layers of one kind is one stacked subtree; no copy. The head is
    untied."""
    runs, r = [], 0
    while f"r{r}_ln1" in w:
        runs.append({k: w[f"r{r}_{k}"] for k in _LAYER if f"r{r}_{k}" in w})
        r += 1
    return {"embed": w["embed"], "runs": tuple(runs), "norm_f": w["norm_f"],
            "head": w["head"]}


def model_of(cfg: dict):
    dtype = jnp.dtype(cfg.get("as_run", {}).get("dtype", "bfloat16"))
    depth = cfg["num_hidden_layers"]
    hd = cfg["head_dim"]
    return HybridLM(
        vocab=cfg["vocab_size"], dim=cfg["hidden_size"],
        mlp_dim=cfg["moe_intermediate_size"],
        mixers=tuple(_KIND[k] for k in cfg["layer_types"]),
        layer_ids=tuple(range(depth)),
        # h += branch: no residual multiplier
        published_depth=1, scale_depth=1.0,
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=hd,
        attn_rope=True, rope_theta=float(cfg["rope_theta"]),
        rotary_dims=int(hd * cfg["partial_rotary_factor"]),
        attn_qk_norm=True, attn_gate=True, eps=float(cfg["rms_norm_eps"]),
        delta_key_heads=cfg["linear_num_key_heads"],
        delta_value_heads=cfg["linear_num_value_heads"],
        delta_key_dim=cfg["linear_key_head_dim"],
        delta_value_dim=cfg["linear_value_head_dim"],
        ssm_conv=cfg["linear_conv_kernel_dim"],
        ffn=MOE, experts=cfg["published"]["num_experts"],
        experts_per_token=cfg["num_experts_per_tok"],
        experts_held=(int(cfg.get("first_local_expert", 0)),
                      cfg["num_experts"]),
        shared_dim=cfg["shared_expert_intermediate_size"], shared_gate=True,
        # a 4096-token prefill chunk's float32 logits over 151936 rows
        # would be 2.5 GB: the head runs over the last real position alone
        last_logits=True, dtype=dtype, param_dtype=dtype)


def build(cfg: dict, w: dict):
    """(model, params, further keyword arguments of `DecodeServer`) over the
    configuration ``cfg`` and the family's weights ``w``."""
    if len(cfg["layer_types"]) != cfg["num_hidden_layers"]:
        raise ValueError("one layer type a layer")
    for key, want in (("decoder_sparse_step", 1), ("mlp_only_layers", []),
                      ("norm_topk_prob", True), ("hidden_act", "silu"),
                      ("rope_scaling", None), ("use_sliding_window", False),
                      ("tie_word_embeddings", False)):
        if cfg[key] != want:
            raise ValueError(f"the program runs {key} = {want!r} only")
    return model_of(cfg), program_params(w), {}

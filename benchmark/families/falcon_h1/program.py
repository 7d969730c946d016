"""Falcon-H1 as the program runs it (`idunno_tpu/models/hybrid.py`: the
`attention+mamba2` kind, both mixers in every layer, and the dense
feed-forward with its two multipliers): the only file of the family that
imports the program."""
from __future__ import annotations

import jax.numpy as jnp

# at the top, not inside `build`: a program whose hybrid stack has no layer
# of both mixers (the parent of the PR that brought this family) then fails
# as the family is loaded, within seconds, and not after the weights are
# drawn
from idunno_tpu.models.hybrid import PARALLEL, HybridLM

_LAYER = ("ln1", "w_in", "conv_w", "conv_b", "dt_bias", "A_log", "D", "norm",
          "w_out", "wq", "wk", "wv", "wo", "ln2", "wg", "wu", "wd")


def derive(cfg: dict) -> dict:
    """What follows from a configuration's sizes where a rehearsal has
    swapped them: nothing is computed, the rehearsal states every size."""
    return dict(cfg)


def program_params(w: dict) -> dict:
    """The same arrays under the program's names: the layers are one run
    (every layer is of the one kind), one stacked subtree; no copy."""
    return {"embed": w["embed"], "runs": ({k: w[k] for k in _LAYER},),
            "norm_f": w["norm_f"], "head": w["head"]}


def model_of(cfg: dict):
    dtype = jnp.dtype(cfg.get("as_run", {}).get("dtype", "bfloat16"))
    depth = cfg["num_hidden_layers"]
    return HybridLM(
        vocab=cfg["vocab_size"], dim=cfg["hidden_size"],
        mlp_dim=cfg["intermediate_size"], mixers=(PARALLEL,) * depth,
        layer_ids=tuple(range(depth)),
        # h += branch: no residual multiplier of the stack's own
        published_depth=1, scale_depth=1.0,
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        attn_rope=True, rope_theta=float(cfg["rope_theta"]),
        key_mult=float(cfg["key_multiplier"]),
        attn_in_mult=float(cfg["attention_in_multiplier"]),
        attn_out_mult=float(cfg["attention_out_multiplier"]),
        ssm_in_mult=float(cfg["ssm_in_multiplier"]),
        ssm_out_mult=float(cfg["ssm_out_multiplier"]),
        ssm_mults=tuple(float(m) for m in cfg["ssm_multipliers"]),
        mlp_mults=tuple(float(m) for m in cfg["mlp_multipliers"]),
        scale_emb=float(cfg["embedding_multiplier"]),
        # logits = (.. @ head) * lm_head_multiplier
        logit_div=1.0 / float(cfg["lm_head_multiplier"]),
        eps=float(cfg["rms_norm_eps"]),
        ssm_heads=cfg["mamba_n_heads"], ssm_head_dim=cfg["mamba_d_head"],
        ssm_state=cfg["mamba_d_state"], ssm_groups=cfg["mamba_n_groups"],
        ssm_conv=cfg["mamba_d_conv"], ssm_chunk=cfg["mamba_chunk_size"],
        # a 2048-token prefill's float32 logits over 261120 rows would be
        # 2.1 GB: the head runs over the last real position alone
        last_logits=True, dtype=dtype, param_dtype=dtype)


def build(cfg: dict, w: dict):
    """(model, params, further keyword arguments of `DecodeServer`) over the
    configuration ``cfg`` and the family's weights ``w``."""
    for key, want in (("attention_bias", False), ("mamba_proj_bias", False),
                      ("projectors_bias", False), ("mlp_bias", False),
                      ("mamba_conv_bias", True), ("mamba_rms_norm", True),
                      ("mamba_norm_before_gate", False),
                      ("mamba_use_mlp", True), ("attn_layer_indices", None),
                      ("rope_scaling", None), ("hidden_act", "silu"),
                      ("tie_word_embeddings", False)):
        if cfg[key] != want:
            raise ValueError(f"the program runs {key} = {want!r} only")
    if cfg["mamba_n_heads"] * cfg["mamba_d_head"] != cfg["mamba_d_ssm"]:
        raise ValueError("mamba_n_heads x mamba_d_head must be mamba_d_ssm")
    return model_of(cfg), program_params(w), {}

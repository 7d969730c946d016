"""granite-4.0-h as the program runs it (`idunno_tpu/models/hybrid.py`: the
`mamba2` and `attention` kinds, the `moe` feed-forward): the only file of
the family that imports the program."""
from __future__ import annotations

import jax.numpy as jnp

# at the top, not inside `build`: a program whose hybrid stack has no
# state-space kind (the parent of the PR that brought this family) then
# fails as the family is loaded, within seconds, and not after the weights
# are drawn
from idunno_tpu.models.hybrid import ATTENTION, MAMBA, MOE, HybridLM

_KIND = {"mamba": MAMBA, "attention": ATTENTION}
_LAYER = ("ln1", "w_in", "conv_w", "conv_b", "dt_bias", "A_log", "D", "norm",
          "w_out", "wq", "wk", "wv", "wo", "ln2", "router", "w1", "w2",
          "ws1", "ws2")


def derive(cfg: dict) -> dict:
    """What follows from a configuration's sizes where a rehearsal has
    swapped them: nothing is computed, the rehearsal states every size."""
    return dict(cfg)


def program_params(w: dict) -> dict:
    """The same arrays under the program's names: a run of consecutive
    layers of one kind is one stacked subtree; no copy. The head is the
    embedding (tied): the program is handed none."""
    runs, r = [], 0
    while f"r{r}_ln1" in w:
        runs.append({k: w[f"r{r}_{k}"] for k in _LAYER if f"r{r}_{k}" in w})
        r += 1
    return {"embed": w["embed"], "runs": tuple(runs), "norm_f": w["norm_f"]}


def model_of(cfg: dict):
    dtype = jnp.dtype(cfg.get("as_run", {}).get("dtype", "bfloat16"))
    depth = cfg["num_hidden_layers"]
    return HybridLM(
        vocab=cfg["vocab_size"], dim=cfg["hidden_size"],
        mlp_dim=cfg["intermediate_size"],
        mixers=tuple(_KIND[k] for k in cfg["layer_types"]),
        layer_ids=tuple(range(depth)),
        # h += residual_multiplier * branch: the stack's residual scale is
        # scale_depth / sqrt(published_depth)
        published_depth=1, scale_depth=float(cfg["residual_multiplier"]),
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["hidden_size"] // cfg["num_attention_heads"],
        attn_scale=float(cfg["attention_multiplier"]),
        scale_emb=float(cfg["embedding_multiplier"]),
        logit_div=float(cfg["logits_scaling"]),
        eps=float(cfg["rms_norm_eps"]),
        ssm_heads=cfg["mamba_n_heads"], ssm_head_dim=cfg["mamba_d_head"],
        ssm_state=cfg["mamba_d_state"], ssm_groups=cfg["mamba_n_groups"],
        ssm_conv=cfg["mamba_d_conv"], ssm_chunk=cfg["mamba_chunk_size"],
        ffn=MOE, experts=cfg["published"]["num_local_experts"],
        experts_per_token=cfg["num_experts_per_tok"],
        experts_held=(int(cfg.get("first_local_expert", 0)),
                      cfg["num_local_experts"]),
        shared_dim=cfg["shared_intermediate_size"],
        dtype=dtype, param_dtype=dtype)


def build(cfg: dict, w: dict):
    """(model, params, further keyword arguments of `DecodeServer`) over the
    configuration ``cfg`` and the family's weights ``w``."""
    if len(cfg["layer_types"]) != cfg["num_hidden_layers"]:
        raise ValueError("one layer type a layer")
    for key, want in (("position_embedding_type", "nope"),
                      ("attention_bias", False), ("mamba_proj_bias", False),
                      ("mamba_conv_bias", True),
                      ("tie_word_embeddings", True)):
        if cfg[key] != want:
            raise ValueError(f"the program runs {key} = {want!r} only")
    return model_of(cfg), program_params(w), {}

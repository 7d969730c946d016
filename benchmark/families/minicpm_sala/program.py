"""MiniCPM-SALA as the program runs it (`idunno_tpu/models/hybrid.py`): the
only file of the family that imports the program."""
from __future__ import annotations

import jax.numpy as jnp

# at the top, not inside `build`: a program that has no hybrid stack (the
# parent of the PR that brought this family) then fails as the family is
# loaded, within seconds, and not after the weights are drawn
from idunno_tpu.models.hybrid import HybridLM

_LAYER = ("ln1", "wq", "wk", "wv", "qn", "kn", "wz", "wo", "ln2", "wg", "wu",
          "wd", "on")


def derive(cfg: dict) -> dict:
    """What follows from a configuration's sizes where a rehearsal has
    swapped them: nothing is computed, the rehearsal states every size."""
    return dict(cfg)


def program_params(w: dict) -> dict:
    """The same arrays under the program's names: a run of consecutive
    layers of one kind is one stacked subtree; no copy."""
    runs, r = [], 0
    while f"r{r}_ln1" in w:
        runs.append({k: w[f"r{r}_{k}"] for k in _LAYER if f"r{r}_{k}" in w})
        r += 1
    return {"embed": w["embed"], "runs": tuple(runs),
            "norm_f": w["norm_f"], "head": w["w_head"]}


def model_of(cfg: dict):
    dtype = jnp.dtype(cfg.get("as_run", {}).get("dtype", "bfloat16"))
    sp = cfg["sparse_config"]
    ids = cfg.get("layer_ids") or list(range(cfg["num_hidden_layers"]))
    return HybridLM(
        vocab=cfg["vocab_size"], dim=cfg["hidden_size"],
        mlp_dim=cfg["intermediate_size"],
        mixers=tuple(cfg["mixer_types"]), layer_ids=tuple(ids),
        published_depth=cfg["mup_denominator"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        lightning_heads=cfg["lightning_nh"],
        lightning_head_dim=cfg["lightning_head_dim"],
        scale_emb=float(cfg["scale_emb"]),
        scale_depth=float(cfg["scale_depth"]),
        logit_div=cfg["hidden_size"] / cfg["dim_model_base"],
        eps=float(cfg["rms_norm_eps"]), rope_theta=float(cfg["rope_theta"]),
        kernel_size=sp["kernel_size"], kernel_stride=sp["kernel_stride"],
        block_size=sp["block_size"], topk=sp["topk"],
        init_blocks=sp["init_blocks"], window_size=sp["window_size"],
        dense_len=sp["dense_len"], dtype=dtype, param_dtype=dtype)


def build(cfg: dict, w: dict):
    """(model, params, further keyword arguments of `DecodeServer`) over the
    configuration ``cfg`` and the family's weights ``w``."""
    if cfg["lightning_nkv"] != cfg["lightning_nh"]:
        raise ValueError("the program's lightning layer has a K/V head a "
                         "query head")
    if len(cfg["mixer_types"]) != cfg["num_hidden_layers"]:
        raise ValueError("one mixer a layer")
    return model_of(cfg), program_params(w), {}

"""StarCoder2 as the program runs it: the only file of the family that
imports the program."""
from __future__ import annotations

import jax.numpy as jnp


def derive(cfg: dict) -> dict:
    """What follows from a configuration's widths where a rehearsal has
    swapped them: the MLP at 4x the width, the head size."""
    cfg = dict(cfg)
    cfg["intermediate_size"] = 4 * cfg["hidden_size"]
    cfg["head_dim"] = cfg["hidden_size"] // cfg["num_attention_heads"]
    return cfg


def program_params(w: dict) -> dict:
    """The same arrays as the program's scanned pool holds them
    (`models/transformer.py:stack_block_params`): no copy."""
    def kb(k, b):
        return {"kernel": w[k], "bias": w[b]}
    return {
        "embed": {"embedding": w["embed"]},
        "blocks": {
            "ln1": {"scale": w["ln1_s"], "bias": w["ln1_b"]},
            "attn": {"q": kb("wq", "bq"), "k": kb("wk", "bk"),
                     "v": kb("wv", "bv"), "out": kb("wo", "bo")},
            "ln2": {"scale": w["ln2_s"], "bias": w["ln2_b"]},
            "mlp_up": kb("w_up", "b_up"),
            "mlp_down": kb("w_down", "b_down"),
        },
        "ln_f": {"scale": w["lnf_s"], "bias": w["lnf_b"]},
        "head": kb("w_head", "b_head"),
    }


def build(cfg: dict, w: dict):
    """(model, params, further keyword arguments of `DecodeServer`) over the
    configuration ``cfg`` and the family's weights ``w``."""
    from idunno_tpu.models.transformer import TransformerLM

    if cfg["intermediate_size"] != 4 * cfg["hidden_size"]:
        raise ValueError("the program's block has its MLP at 4x the width")
    dtype = jnp.dtype(cfg.get("as_run", {}).get("dtype", "bfloat16"))
    model = TransformerLM(
        vocab=cfg["vocab_size"], dim=cfg["hidden_size"],
        depth=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        dtype=dtype, param_dtype=dtype,
        # the pool stacks per-block params itself; handing it the stacked
        # layout saves the transient second copy of the weights
        scan_layers=True)
    return model, program_params(w), {}

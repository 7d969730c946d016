"""Static HLO copy census for the speculative-round program.

The round-5 decode fix was found by exactly this analysis (a post-scatter
select kept the pre-scatter KV cache live -> full-cache copy per layer per
step; RESULTS.md "Decode-path diagnosis"). The 2026-08-01 recapture shows
the PLAIN path fixed (2.7x) but fused speculation still 0.41x at the
constructed-acceptance ceiling -- ~30 ms per round vs 2.5 ms per plain
step at the same shapes, far above the cost of one verify apply plus
gamma draft steps. This tool compiles both programs on CPU at reduced
shapes and counts cache-sized copy/fusion-output buffers in the optimized
HLO so the per-round overhead can be attributed statically, without
chip time.

Usage:  JAX_PLATFORMS=cpu python tools/spec_copy_census.py
"""
from __future__ import annotations

import os
import re
import sys
from collections import Counter

import jax

jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from idunno_tpu.engine.serve_lm import DecodeServer  # noqa: E402
from idunno_tpu.models.transformer import TransformerLM  # noqa: E402

# reduced bench shapes: cache [slots, max_len, heads, head_dim] stays the
# dominant buffer; vocab/dim shrink only the weight tensors
SLOTS, MAX_LEN, DIM, DEPTH, HEADS, VOCAB = 16, 512, 128, 2, 4, 1024
DDIM, DDEPTH, GAMMA = 64, 1, 4


def cache_shapes(model: TransformerLM, slots: int, max_len: int):
    hd = model.dim // model.num_heads
    kvh = model.num_kv_heads or model.num_heads
    return {(slots, max_len, kvh, hd)}


def census(hlo: str, shapes: set[tuple]) -> Counter:
    """Count ops whose OUTPUT is a cache-shaped buffer, by opcode."""
    pats = {s: re.compile(
        r"(?:bf16|f32|f16|s8)\[" + ",".join(map(str, s)) + r"\]")
        for s in shapes}
    out: Counter = Counter()
    for line in hlo.splitlines():
        # %name = f32[16,512,4,32]{3,2,1,0} opcode(...)
        m = re.search(r"=\s*(\S+\[[\d,]*\]\S*)\s+([\w-]+)\(", line)
        if not m:
            continue
        ty, op = m.group(1), m.group(2)
        for s, pat in pats.items():
            if pat.search(ty):
                out[op] += 1
                break
    return out


def main() -> None:
    model = TransformerLM(vocab=VOCAB, dim=DIM, depth=DEPTH,
                          num_heads=HEADS, causal=True)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    draft = TransformerLM(vocab=VOCAB, dim=DDIM, depth=DDEPTH,
                          num_heads=2, causal=True)
    dparams = draft.init(jax.random.PRNGKey(1),
                         jnp.zeros((1, 8), jnp.int32))["params"]

    shapes = cache_shapes(model, SLOTS, MAX_LEN)

    plain = DecodeServer(model, params, slots=SLOTS, prompt_len=8,
                         max_len=MAX_LEN, decode_steps=8)
    spec = DecodeServer(model, params, slots=SLOTS, prompt_len=8,
                        max_len=MAX_LEN, decode_steps=2,
                        draft=(draft, dparams), draft_len=GAMMA)
    for name, srv in (("plain", plain), ("spec", spec)):
        for t in ([1, 2, 3], [4, 5]):
            srv.submit(t, max_new=8)
        srv._retire_finished(); srv._admit()
        if name == "plain":
            lowered = srv._decode.lower(
                srv.params, srv._tokens, srv._cache, srv._cursors,
                srv._remaining, srv._temps, srv._top_ps, srv._top_ks,
                srv._keys, srv._logprobs, srv._pres, srv._freq,
                srv._counts)
        else:
            lowered = srv._decode_spec.lower(
                srv.params, srv._draft_params, srv._tokens, srv._cache,
                srv._draft_cache, srv._cursors, srv._remaining,
                srv._temps, srv._top_ps, srv._top_ks, srv._keys,
                srv._logprobs)
        prog = lowered.compile().as_text()
        c = census(prog, shapes)
        n_while = prog.count(" while(")
        print(f"{name}: cache-shaped op outputs {dict(c)}; "
              f"while loops {n_while}; hlo lines {len(prog.splitlines())}")


if __name__ == "__main__":
    main()

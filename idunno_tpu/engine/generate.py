"""Autoregressive LM serving: KV-cached greedy/temperature decoding.

The reference serves only feed-forward image classifiers
(`alexnet_resnet.py:12-92`); a complete framework must also *serve* its
sequence family, not just train it. TPU-first structure: the whole decode —
prompt prefill and generation — is ONE jitted `lax.fori_loop` over a
static-shape token buffer, with per-layer KV caches carried in the flax
"cache" collection (`models.transformer.MultiHeadAttention._decode_step`).
No per-token Python round-trips, no dynamic shapes, no recompiles across
calls with the same (batch, lengths) signature.

Each step costs O(max_len · d) attention against the static cache — the
KV-cache linear-decode path — instead of the O(t²) full re-forward a naive
generate would pay.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from idunno_tpu.models.transformer import (TransformerLM, decode_apply,
                                           scan_compatible,
                                           stack_block_params)


def decode_model(model: TransformerLM, max_len: int) -> TransformerLM:
    """The single-token serving twin of a trained model: same params tree,
    decode-mode attention with a ``max_len`` KV cache."""
    return dataclasses.replace(model, decode=True, max_decode_len=max_len)


def init_cache(model: TransformerLM, batch: int, max_len: int) -> Any:
    """Zeroed per-layer KV caches for a [batch] decode of ≤ max_len tokens.
    Shapes come from `jax.eval_shape` (no parameter init or forward compute
    is traced — the cache is zeros by construction).

    ``scan_layers=True`` models get the scanned layout: ONE per-block
    subtree whose leaves carry a leading depth axis (shapes from the
    unscanned twin's block0 — scan-compatible models have homogeneous
    blocks, so block0 names every layer's shapes)."""
    dec = decode_model(model, max_len)
    if hasattr(dec, "init_cache"):     # a stack that lays out its own cache
        return dec.init_cache(batch)   # (`models/hybrid.py`)
    if getattr(model, "scan_layers", False):
        flat = dataclasses.replace(dec, scan_layers=False)
        shapes = jax.eval_shape(flat.init, jax.random.PRNGKey(0),
                                jnp.zeros((batch, 1), jnp.int32))
        return jax.tree.map(
            lambda s: jnp.zeros((model.depth,) + s.shape, s.dtype),
            shapes["cache"]["block0"])
    shapes = jax.eval_shape(dec.init, jax.random.PRNGKey(0),
                            jnp.zeros((batch, 1), jnp.int32))
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        shapes["cache"])


@partial(jax.jit,
         static_argnames=("model", "prompt_len", "max_new", "temperature",
                          "top_p", "top_k", "presence_penalty",
                          "frequency_penalty"))
def generate(model: TransformerLM, params: Any, prompt: jnp.ndarray,
             prompt_len: int, max_new: int, *, temperature: float = 0.0,
             top_p: float = 1.0, top_k: int = 0,
             presence_penalty: float = 0.0,
             frequency_penalty: float = 0.0,
             rng: jax.Array | None = None,
             prompt_lens: jnp.ndarray | None = None) -> jnp.ndarray:
    """Generate ``max_new`` tokens after ``prompt[:, :prompt_len]``.

    prompt: int32 [B, prompt_len] (static width). Returns int32
    [B, prompt_len + max_new]. temperature 0 → greedy argmax; > 0 →
    softmax sampling (needs ``rng``); ``top_p`` < 1 restricts sampling to
    the nucleus — the smallest probability mass ≥ top_p (applied after
    temperature); ``top_k`` > 0 first restricts to the k most probable
    tokens (standard warper order: top-k, then nucleus over the
    renormalized top-k distribution — `ops.sampling.filtered_probs`).
    ``presence_penalty``/``frequency_penalty`` subtract
    ``presence·1[count>0] + frequency·count`` from every token's raw
    logit, where count is over this row's GENERATED tokens only (prompt
    tokens are not penalized — vLLM semantics); applied before
    temperature/filters and to greedy picks alike.

    Ragged batches: pass ``prompt_lens`` (int [B], 1 ≤ len ≤ prompt_len)
    with right-padded prompts — each row is teacher-forced only through its
    own true length and generates from there, so its output occupies
    positions [prompt_lens[r], prompt_len + max_new); every row still gets
    ≥ max_new generated tokens. One compile serves all length mixes (the
    lengths are a traced array, not a static argument).
    """
    if prompt.shape[1] != prompt_len:
        raise ValueError(f"prompt is [B, {prompt.shape[1]}] but "
                         f"prompt_len={prompt_len}; slice/pad upstream")
    b = prompt.shape[0]
    total = prompt_len + max_new
    dec = decode_model(model, total)
    if scan_compatible(model) and not getattr(model, "scan_layers", False):
        # run the SAME scanned step the serving pool runs (decode_apply),
        # so the pool's token-exactness tests compare like with like; the
        # one-time param stack is traced into the program ahead of the
        # decode loop — one weight copy per generate call
        dec = dataclasses.replace(dec, scan_layers=True)
        if "blocks" in params and "block0" not in params:
            pass    # already in the stacked layout (e.g. a pool's params)
        else:
            params = stack_block_params(params, model.depth)
    cache = init_cache(dec, b, total)
    tokens = jnp.concatenate(
        [prompt.astype(jnp.int32),
         jnp.zeros((b, max_new), jnp.int32)], axis=1)       # [B, total]
    if rng is None:
        rng = jax.random.PRNGKey(0)
    plens = (jnp.full((b,), prompt_len, jnp.int32) if prompt_lens is None
             else prompt_lens.astype(jnp.int32))

    penalized = presence_penalty != 0.0 or frequency_penalty != 0.0
    counts0 = jnp.zeros((b, model.vocab if penalized else 0), jnp.int32)

    def step(t, carry):
        tokens, cache, rng, counts = carry
        tok = jax.lax.dynamic_slice(tokens, (0, t), (b, 1))  # current input
        logits, cache = decode_apply(dec, params, cache, tok)
        logits = logits[:, 0]                                # [B, vocab]
        if penalized:   # static: counts over generated tokens only
            logits = (logits
                      - presence_penalty * (counts > 0)
                      - frequency_penalty * counts.astype(logits.dtype))
        if temperature > 0.0:
            scaled = logits / temperature
            if top_p < 1.0 or top_k > 0:
                # top-k then nucleus: mask everything outside the shared
                # survivor set (`ops.sampling.sample_keep_mask` — the
                # SAME mask the serving tail builds, so serve-vs-generate
                # token-exactness is structural) as -inf; the categorical
                # draw below is unchanged
                from idunno_tpu.ops.sampling import sample_keep_mask
                keep = sample_keep_mask(
                    scaled, jnp.full((b,), top_p),
                    jnp.full((b,), top_k, jnp.int32))
                scaled = jnp.where(keep, scaled, -jnp.inf)
            rng, sub = jax.random.split(rng)
            nxt = jax.random.categorical(sub, scaled, axis=-1)
        else:
            nxt = jnp.argmax(logits, axis=-1)
        # per row: teacher-force while inside its prompt; append past it
        write_at = jnp.minimum(t + 1, total - 1)
        keep_prompt = (t + 1) < plens                        # [B]
        cur = jax.lax.dynamic_slice(tokens, (0, write_at), (b, 1))[:, 0]
        nxt = jnp.where(keep_prompt, cur, nxt.astype(jnp.int32))
        tokens = jax.lax.dynamic_update_slice(
            tokens, nxt[:, None], (0, write_at))
        if penalized:   # teacher-forced (prompt) tokens never count
            counts = counts.at[jnp.arange(b), nxt].add(
                jnp.where(keep_prompt, 0, 1))
        return tokens, cache, rng, counts

    tokens, _, _, _ = jax.lax.fori_loop(0, total - 1, step,
                                        (tokens, cache, rng, counts0))
    return tokens


@partial(jax.jit,
         static_argnames=("model", "prompt_len", "max_new", "beam_width"))
def beam_search(model: TransformerLM, params: Any, prompt: jnp.ndarray,
                prompt_len: int, max_new: int, *,
                beam_width: int = 4) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Beam-search decoding with the same KV cache as `generate`.

    prompt int32 [B, prompt_len] → (sequences int32 [B, prompt_len +
    max_new], total log-prob [B]) for the best beam. One jitted program:
    the prompt prefills the cache at batch B (paid once, not per beam),
    the cache is then replicated to B·W rows, and each generated position
    keeps the top W of the W·V continuations, re-gathering the KV caches
    to follow their parent beams. (No EOS handling: all beams have length
    max_new, so scores are directly comparable log-probs.)
    """
    if prompt.shape[1] != prompt_len:
        raise ValueError(f"prompt is [B, {prompt.shape[1]}] but "
                         f"prompt_len={prompt_len}; slice/pad upstream")
    b = prompt.shape[0]
    w = beam_width
    total = prompt_len + max_new
    neg_inf = jnp.asarray(-1e30, jnp.float32)
    dec = decode_model(model, total)

    # -- prefill at batch B: feed prompt tokens 0..prompt_len-2 ----------
    cache_b = init_cache(model, b, total)

    def prefill(t, cache):
        tok = jax.lax.dynamic_slice(prompt.astype(jnp.int32), (0, t),
                                    (b, 1))
        _, mutated = dec.apply({"params": params, "cache": cache}, tok,
                               mutable=["cache"])
        return mutated["cache"]

    cache_b = jax.lax.fori_loop(0, prompt_len - 1, prefill, cache_b)

    # -- replicate to B*W beams (row-major [b0w0..b0wW-1, b1w0, ...]) ----
    cache = jax.tree.map(
        lambda a: (jnp.repeat(a, w, axis=0)
                   if a.ndim > 0 and a.shape[0] == b else a), cache_b)
    tokens = jnp.repeat(jnp.concatenate(
        [prompt.astype(jnp.int32), jnp.zeros((b, max_new), jnp.int32)],
        axis=1), w, axis=0)                            # [B*W, total]
    # only beam 0 is live before the first expansion (identical beams
    # would multiply-count the same continuation)
    scores = jnp.tile(jnp.where(jnp.arange(w) == 0, 0.0, neg_inf), b)

    def gather_beams(tree, parent):                    # parent [B, W]
        flat = (jnp.arange(b)[:, None] * w + parent).reshape(-1)
        return jax.tree.map(
            lambda a: a[flat] if a.ndim > 0 and a.shape[0] == b * w else a,
            tree)

    def step(t, carry):
        tokens, cache, scores = carry
        tok = jax.lax.dynamic_slice(tokens, (0, t), (b * w, 1))
        logits, mutated = dec.apply({"params": params, "cache": cache},
                                    tok, mutable=["cache"])
        cache = mutated["cache"]
        logp = jax.nn.log_softmax(logits[:, 0].astype(jnp.float32), -1)
        vocab = logp.shape[-1]
        cand = (scores[:, None] + logp).reshape(b, w * vocab)
        new_scores, flat_idx = jax.lax.top_k(cand, w)          # [B, W]
        parent = flat_idx // vocab                     # beam each came from
        nxt = (flat_idx % vocab).astype(jnp.int32)
        tokens = gather_beams(tokens, parent)
        cache = gather_beams(cache, parent)
        tokens = jax.lax.dynamic_update_slice(
            tokens, nxt.reshape(-1, 1), (0, t + 1))
        return tokens, cache, new_scores.reshape(-1)

    tokens, _, scores = jax.lax.fori_loop(prompt_len - 1, total - 1, step,
                                          (tokens, cache, scores))
    scores = scores.reshape(b, w)
    best = jnp.argmax(scores, axis=1)                  # [B]
    seqs = tokens.reshape(b, w, total)[jnp.arange(b), best]
    return seqs, scores[jnp.arange(b), best]


# -- LM persistence: a servable (config + params) unit in the store --------
#
# The image engine reconstructs its models from the registry by name; LMs
# carry their hyperparameters with the checkpoint instead, so any node can
# reconstruct the module and serve `generate` without out-of-band config.
# Dense AND switch-MoE architectures persist (the MoE factory publishes a
# declarative twin, `moe.switch_ffn_factory(...).lm_store_ffn`); custom
# attn_fn / ffn_factory closures are code, not data, and save_lm refuses
# both (swap a numerically-equivalent kernel for full_attention first).
# Config and weights live in ONE versioned store object (length-prefixed
# JSON header + flax bytes), so a save is atomic and any historical version
# pairs its architecture with its own weights.

_LM_CONFIG_FIELDS = ("vocab", "dim", "depth", "num_heads",
                     "num_kv_heads", "causal", "ffn_every",
                     "kv_cache_dtype", "remat")


def lm_store_name(name: str) -> str:
    return f"lm/{name}"


def save_lm(store, name: str, model: TransformerLM, params: Any) -> int:
    """Version a TransformerLM (architecture + weights, one atomic object)
    into the replicated store under ``lm/<name>``; returns the store
    version. Dense and switch-MoE FFNs are storable; a custom
    ``ffn_factory`` without a declarative ``lm_store_ffn`` twin is code
    and is refused."""
    import json
    import struct

    import flax.serialization

    from idunno_tpu.parallel.ring_attention import full_attention

    config = {f: getattr(model, f) for f in _LM_CONFIG_FIELDS}
    if model.attn_fn is not full_attention:
        # silently dropping it would make load_lm rebuild a DIFFERENT
        # model (default attention); numerically-equivalent kernels can be
        # swapped explicitly before saving:
        # dataclasses.replace(model, attn_fn=full_attention)
        raise ValueError(
            "save_lm stores models with the default full_attention only "
            "(a custom attn_fn is code, not serializable config; replace "
            "it with full_attention before saving if it is numerically "
            "equivalent)")
    if model.ffn_factory is not None:
        ffn = getattr(model.ffn_factory, "lm_store_ffn", None)
        if ffn is None:
            raise ValueError(
                "save_lm stores dense or switch-MoE LMs only (this custom "
                "ffn_factory is code, not serializable config)")
        config["ffn"] = dict(ffn)
    config["dtype"] = jnp.dtype(model.dtype).name
    config["param_dtype"] = jnp.dtype(model.param_dtype).name
    header = json.dumps(config).encode()
    host_params = jax.tree.map(jax.device_get, params)
    blob = (struct.pack(">I", len(header)) + header
            + flax.serialization.to_bytes(host_params))
    return store.put_bytes(lm_store_name(name), blob)


def load_lm(store, name: str,
            version: int | None = None) -> tuple[TransformerLM, Any]:
    """Reconstruct a stored LM on any node (latest or one historical
    version): returns (model, params) — the version's own architecture is
    paired with its own weights."""
    import json
    import struct

    import flax.serialization

    blob, _ = store.get_bytes(lm_store_name(name), version=version)
    hlen = struct.unpack(">I", blob[:4])[0]
    config = json.loads(blob[4:4 + hlen])
    config["dtype"] = jnp.dtype(config["dtype"])
    config["param_dtype"] = jnp.dtype(config["param_dtype"])
    ffn = config.pop("ffn", None)
    if ffn is not None:
        kind = ffn.pop("kind", None)
        if kind != "switch":
            raise ValueError(f"stored LM {name!r} uses unknown ffn kind "
                             f"{kind!r}")
        from idunno_tpu.models.moe import switch_ffn_factory
        config["ffn_factory"] = switch_ffn_factory(
            n_experts=int(ffn["n_experts"]),
            capacity_factor=float(ffn["capacity_factor"]),
            hidden_ratio=int(ffn["hidden_ratio"]), k=int(ffn["k"]))
    model = TransformerLM(**config)
    # structure-only template (no init compute, mirrors init_cache)
    template = jax.eval_shape(
        model.init, jax.random.PRNGKey(0),
        jnp.zeros((1, 8), jnp.int32))["params"]
    template = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), template)
    params = flax.serialization.from_bytes(template, blob[4 + hlen:])
    return model, params


def stepwise_logits(model: TransformerLM, params: Any,
                    tokens: jnp.ndarray) -> jnp.ndarray:
    """Teacher-forced single-token decode over a full [B, T] sequence,
    returning [B, T, vocab] — must equal the batched full forward; the
    correctness oracle for the cache (tests)."""
    b, t = tokens.shape
    dec = decode_model(model, t)
    cache = init_cache(model, b, t)
    outs = []
    for i in range(t):
        logits, mutated = dec.apply({"params": params, "cache": cache},
                                    tokens[:, i:i + 1], mutable=["cache"])
        cache = mutated["cache"]
        outs.append(logits[:, 0])
    return jnp.stack(outs, axis=1)

"""The plain reference: StarCoder2's forward pass in straightforward
`jax.numpy`, float32, matrix products at `highest` precision, no cache, no
batching, no kernel. It imports nothing of the program and computes what the
configuration file states under `as_run` (RoPE base, LayerNorm epsilon, an
untied head). It runs layer by layer over the benchmark's own stacked weights
so that it fits beside them on the chip.

``quant="fp8"`` is the control: the same pass with every matrix product's
two inputs rounded to float8 (e4m3), the nearest precision below the bf16 the
configuration states.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

_QBLOCK = 512          # query rows per attention block
_PAD = 512             # sequences are padded to a multiple of this


def _fq(x, quant: str | None, axis: int = -1):
    """Round ``x`` to the control's precision and back (per-``axis`` scale
    to float8's range); a no-op for the reference itself."""
    if quant is None:
        return x
    if quant != "fp8":
        raise ValueError(f"unknown control precision {quant!r}")
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s == 0.0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _layer_norm(x, scale, bias, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def _rope(x, positions, base):
    """Rotate-half rotary embedding over [T, H, D]."""
    half = x.shape[-1] // 2
    freqs = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v, quant):
    """Causal grouped-query attention, [T, H, D] x [T, KVH, D], in blocks of
    query rows so the scores stay small."""
    t, h, d = q.shape
    kvh = k.shape[1]
    g = h // kvh
    qb = q.reshape(t // _QBLOCK, _QBLOCK, kvh, g, d)
    kq, vq = _fq(k, quant), _fq(v, quant)
    cols = jnp.arange(t)

    def block(args):
        i, qi = args
        rows = i * _QBLOCK + jnp.arange(_QBLOCK)
        s = jnp.einsum("qhgd,thd->hgqt", _fq(qi, quant), kq) / (d ** 0.5)
        s = jnp.where(cols[None, None, None, :] <= rows[None, None, :, None],
                      s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hgqt,thd->qhgd", _fq(p, quant), vq)

    out = jax.lax.map(block, (jnp.arange(t // _QBLOCK), qb))
    return out.reshape(t, h, d)


@partial(jax.jit, static_argnames=("eps", "base", "quant"))
def _layer(x, w, l, positions, *, eps, base, quant):
    def at(name):
        return jax.lax.dynamic_index_in_dim(
            w[name], l, 0, keepdims=False).astype(jnp.float32)

    with jax.default_matmul_precision("highest"):
        hn = _layer_norm(x, at("ln1_s"), at("ln1_b"), eps)
        hq = _fq(hn, quant)
        q = jnp.einsum("td,dhk->thk", hq, _fq(at("wq"), quant, 0)) + at("bq")
        k = jnp.einsum("td,dhk->thk", hq, _fq(at("wk"), quant, 0)) + at("bk")
        v = jnp.einsum("td,dhk->thk", hq, _fq(at("wv"), quant, 0)) + at("bv")
        q, k = _rope(q, positions, base), _rope(k, positions, base)
        a = _attention(q, k, v, quant)
        wo = at("wo")
        x = x + jnp.einsum("thk,hkd->td", _fq(a.reshape(a.shape[0], -1),
                                             quant).reshape(a.shape),
                           _fq(wo.reshape(-1, wo.shape[-1]), quant, 0
                               ).reshape(wo.shape)) + at("bo")
        hn = _layer_norm(x, at("ln2_s"), at("ln2_b"), eps)
        up = _fq(hn, quant) @ _fq(at("w_up"), quant, 0) + at("b_up")
        act = jax.nn.gelu(up, approximate=True)
        return x + _fq(act, quant) @ _fq(at("w_down"), quant, 0) + at("b_down")


@jax.jit
def _embed(table, toks):
    return table[toks].astype(jnp.float32)


@partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x, want, w, *, eps, quant):
    with jax.default_matmul_precision("highest"):
        hn = _layer_norm(x[want], w["lnf_s"].astype(jnp.float32),
                         w["lnf_b"].astype(jnp.float32), eps)
        return (_fq(hn, quant) @ _fq(w["w_head"].astype(jnp.float32), quant, 0)
                + w["b_head"].astype(jnp.float32))


def logits_at(w: dict, cfg: dict, tokens, positions_wanted,
              quant: str | None = None):
    """Float32 logits of the reference at ``positions_wanted`` (indices into
    ``tokens``) after one full forward pass over ``tokens``."""
    run = cfg.get("as_run", {})
    eps = float(run.get("norm_epsilon", cfg.get("norm_epsilon", 1e-5)))
    base = float(run.get("rope_theta", cfg.get("rope_theta", 10000.0)))
    n = len(tokens)
    t = -(-n // _PAD) * _PAD
    # padded on the host, so that every length of one bucket runs the same
    # few programs
    toks = np.zeros((t,), np.int32)
    toks[:n] = tokens
    x = _embed(w["embed"], toks)
    pos = np.arange(t, dtype=np.int32)
    layer_w = {k: v for k, v in w.items()
               if k not in ("embed", "lnf_s", "lnf_b", "w_head", "b_head")}
    for l in range(cfg["num_hidden_layers"]):
        x = _layer(x, layer_w, jnp.int32(l), pos, eps=eps, base=base,
                   quant=quant)
    m = len(positions_wanted)
    want = np.zeros((-(-m // 128) * 128,), np.int32)
    want[:m] = positions_wanted
    out = _head(x, want, {k: w[k] for k in ("lnf_s", "lnf_b", "w_head",
                                            "b_head")}, eps=eps, quant=quant)
    return np.asarray(out)[:m]

"""The plain reference: MiniCPM-SALA's forward pass in straightforward
`jax.numpy`, float32, matrix products at `highest` precision, no cache, no
chunked scan, no batching, no kernel. It imports nothing of the program.

The stack is a list of layers of two kinds (`mixer_types`), each a pre-norm
block `h += a * Mixer(RMSNorm(h)); h += a * MLP(RMSNorm(h))` with
`a = scale_depth / sqrt(published depth)`:

* ``lightning-attn``: the decayed linear recurrence `S_t = exp(-s) S_{t-1}
  + k_t^T v_t`, `o_t = q_t S_t / sqrt(d)`, computed here in its exact
  quadratic form `o_t = sum_{j<=t} exp(-s (t-j)) (q_t . k_j) v_j / sqrt(d)`,
  in blocks of queries;
* ``minicpm4``: InfLLM-v2 block-sparse attention. Keys are mean-pooled over
  `kernel_size` tokens every `kernel_stride` (whole spans only); a query
  whose context is longer than `dense_len` scores the pooled keys that end
  at or before it (softmax over them, summed over the heads of its KV
  group), takes a block's score as the largest among the pooled keys that
  overlap it, and attends block 0, the blocks that cover the last
  `window_size` tokens and the `topk` best of the others; a shorter context
  attends everything before it.

It runs layer by layer over the benchmark's own stacked weights so that it
fits beside them on the chip. ``quant="fp8"`` is the control: the same pass
with every matrix product's two inputs rounded to float8 (e4m3), the nearest
precision below the bf16 the configuration states.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

_QBLOCK = 256          # query rows per attention block
# sequences are padded to a multiple of this: a coarse one, so that the
# 8k-16k-token requests of a check run five programs a layer kind, not
# eighteen (their compiles were half of a check's time and crowded the
# persistent cache)
_PAD = 2048


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


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * scale


def _rope(x, positions, base):
    """Rotate-half rotary embedding over [T, H, D]."""
    half = x.shape[-1] // 2
    freqs = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def slopes(cfg: dict, layer_id: int) -> np.ndarray:
    """Lightning Attention's decay rates of one layer, a head each:
    `2^(-8 (h+1) / H) * (1 - l / (L - 1) + 1e-5)` with `l` the layer's
    published index and `L` the published depth."""
    heads = cfg["lightning_nh"]
    depth = cfg.get("published", {}).get("num_hidden_layers",
                                         cfg["num_hidden_layers"])
    base = 2.0 ** (-8.0 * (np.arange(heads) + 1) / heads)
    return (base * (1.0 - layer_id / (depth - 1) + 1e-5)).astype(np.float32)


def _lightning(q, k, v, slope, quant):
    """[T, H, D] each -> [T, H, D]: the decayed causal product."""
    t, h, d = q.shape
    kq, vq = _fq(k, quant), _fq(v, quant)
    cols = jnp.arange(t)

    def block(args):
        i, qi = args
        rows = i * _QBLOCK + jnp.arange(_QBLOCK)
        dist = rows[:, None] - cols[None, :]                     # [Q, T]
        decay = jnp.where(dist >= 0, jnp.exp(
            -slope[:, None, None] * jnp.maximum(dist, 0)[None]), 0.0)
        s = jnp.einsum("qhd,thd->hqt", _fq(qi, quant), kq) * decay
        return jnp.einsum("hqt,thd->qhd", _fq(s, quant), vq) / (d ** 0.5)

    out = jax.lax.map(block, (jnp.arange(t // _QBLOCK),
                              q.reshape(t // _QBLOCK, _QBLOCK, h, d)))
    return out.reshape(t, h, d)


def pooled_keys(k, sp: dict):
    """[T, K, D] -> [NK, K, D]: kernel j is the mean of the keys of tokens
    [stride j, stride j + kernel), for the spans that are whole."""
    ks, st = sp["kernel_size"], sp["kernel_stride"]
    nk = (k.shape[0] - ks) // st + 1
    idx = st * jnp.arange(nk)[:, None] + jnp.arange(ks)[None, :]
    return jnp.mean(k[idx], axis=1)


def block_scores(p, sp: dict, nblocks: int):
    """[..., NK] kernel scores -> [..., nblocks]: a block's score is the
    largest among the kernels that overlap it (absent kernels count 0)."""
    ks, st, bs = sp["kernel_size"], sp["kernel_stride"], sp["block_size"]
    r, back = bs // st, ks // st - 1
    # block b is overlapped by the kernels r b - back .. r b + r - 1
    need = back + r * nblocks
    pad = [(0, 0)] * (p.ndim - 1) + [(back, max(0, need - back - p.shape[-1]))]
    pp = jnp.pad(p, pad)[..., :need]
    return jnp.stack([pp[..., o::r][..., :nblocks]
                      for o in range(r + back)]).max(axis=0)


def selected_blocks(score, t, sp: dict):
    """[..., NB] block scores of the query at position ``t`` -> bool
    [..., NB], the blocks it attends: all up to its own where its context
    is within `dense_len`; else block 0 .. `init_blocks`, those that cover
    the last `window_size` tokens and the `topk` best of the others."""
    bs, topk = sp["block_size"], sp["topk"]
    nb = score.shape[-1]
    b = jnp.arange(nb)
    mine = t // bs
    w0 = jnp.maximum(t - sp["window_size"] + 1, 0) // bs
    forced = (b < sp["init_blocks"]) | (b >= w0)
    others = ~forced
    order = jnp.argsort(-jnp.where(others, score, -jnp.inf), axis=-1)
    best = order[..., :topk]
    picked = jnp.any(best[..., None] == b, axis=-2) & others
    sparse = (forced | picked) & (b <= mine)
    return jnp.where(t + 1 > sp["dense_len"], sparse, b <= mine)


def _sparse_attention(q, k, v, sp: dict, quant):
    """[T, H, D] x [T, K, D] -> [T, H, D]: causal grouped-query attention
    over the blocks each query selects, in blocks of queries."""
    t, h, d = q.shape
    kvh = k.shape[1]
    g = h // kvh
    bs = sp["block_size"]
    nb = t // bs
    kq, vq = _fq(k, quant), _fq(v, quant)
    kbar = _fq(pooled_keys(k, sp), quant)                        # [NK, K, D]
    nk = kbar.shape[0]
    ends = sp["kernel_stride"] * jnp.arange(nk) + sp["kernel_size"] - 1
    cols = jnp.arange(t)

    def block(args):
        i, qi = args                                             # [Q, K, G, D]
        rows = i * _QBLOCK + jnp.arange(_QBLOCK)
        qi = _fq(qi, quant)
        ls = jnp.einsum("qkgd,jkd->kgqj", qi, kbar) / (d ** 0.5)
        ok = ends[None, :] <= rows[:, None]                      # [Q, NK]
        ls = jnp.where(ok, ls, -jnp.inf)
        m = jnp.max(ls, axis=-1, keepdims=True)
        e = jnp.where(ok, jnp.exp(ls - jnp.where(jnp.isfinite(m), m, 0.0)),
                      0.0)
        den = jnp.sum(e, axis=-1, keepdims=True)
        p = jnp.sum(e / jnp.where(den > 0, den, 1.0), axis=1)    # [K, Q, NK]
        sel = selected_blocks(block_scores(p, sp, nb),
                              rows[None, :, None], sp)           # [K, Q, NB]
        mask = (jnp.repeat(sel, bs, axis=-1)
                & (cols[None, None, :] <= rows[None, :, None]))  # [K, Q, T]
        s = jnp.einsum("qkgd,tkd->kgqt", qi, kq) / (d ** 0.5)
        s = jnp.where(mask[:, None], s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgqt,tkd->qkgd", _fq(w, quant), vq)

    out = jax.lax.map(block, (jnp.arange(t // _QBLOCK),
                              q.reshape(t // _QBLOCK, _QBLOCK, kvh, g, d)))
    return out.reshape(t, h, d)


def _static(cfg: dict) -> tuple:
    sp = cfg["sparse_config"]
    return (float(cfg["rms_norm_eps"]), float(cfg["rope_theta"]),
            float(cfg["scale_depth"]) / float(cfg["mup_denominator"]) ** 0.5,
            tuple(sorted(sp.items())))


@partial(jax.jit, static_argnames=("kind", "static", "quant"))
def _layer(x, w, l, positions, slope, *, kind, static, quant):
    eps, theta, a, sp = static
    sp = dict(sp)

    def at(name):
        return jax.lax.dynamic_index_in_dim(
            w[name], l, 0, keepdims=False).astype(jnp.float32)

    def proj(hq, name):
        return jnp.einsum("td,dhk->thk", hq, _fq(at(name), quant, 0))

    with jax.default_matmul_precision("highest"):
        hq = _fq(_rms(x, at("ln1"), eps), quant)
        q = _rms(proj(hq, "wq"), at("qn"), eps)
        k = _rms(proj(hq, "wk"), at("kn"), eps)
        v = proj(hq, "wv")
        gate = jax.nn.sigmoid(proj(hq, "wz"))
        if kind == "lightning-attn":
            q, k = _rope(q, positions, theta), _rope(k, positions, theta)
            o = _rms(_lightning(q, k, v, slope, quant), at("on"), eps)
        else:
            o = _sparse_attention(q, k, v, sp, quant)
        o = o * gate
        wo = at("wo")
        x = x + a * jnp.einsum(
            "thk,hkd->td", _fq(o.reshape(o.shape[0], -1), quant
                               ).reshape(o.shape),
            _fq(wo.reshape(-1, wo.shape[-1]), quant, 0).reshape(wo.shape))
        hq = _fq(_rms(x, at("ln2"), eps), quant)
        act = jax.nn.silu(hq @ _fq(at("wg"), quant, 0)) * (
            hq @ _fq(at("wu"), quant, 0))
        return x + a * (_fq(act, quant) @ _fq(at("wd"), quant, 0))


@partial(jax.jit, static_argnames=("scale",))
def _embed(table, toks, *, scale):
    return table[toks].astype(jnp.float32) * scale


@partial(jax.jit, static_argnames=("eps", "div", "quant"))
def _head(x, want, norm, w_head, *, eps, div, quant):
    with jax.default_matmul_precision("highest"):
        hn = _rms(x[want], norm.astype(jnp.float32), eps) / div
        return _fq(hn, quant) @ _fq(w_head.astype(jnp.float32), quant, 0)


def runs_of(cfg: dict) -> list[tuple[str, list[int]]]:
    """Consecutive layers of one kind: [(kind, their published indices)].
    The weights are stacked a run at a time under `r<i>_<name>`."""
    ids = cfg.get("layer_ids") or list(range(cfg["num_hidden_layers"]))
    out: list[tuple[str, list[int]]] = []
    for kind, lid in zip(cfg["mixer_types"], ids):
        if out and out[-1][0] == kind:
            out[-1][1].append(lid)
        else:
            out.append((kind, [lid]))
    return out


def logits_at(w: dict, cfg: dict, tokens, positions_wanted,
              quant: str | None = None):
    """Float32 logits of the reference at ``positions_wanted`` (indices into
    ``tokens``) after one full forward pass over ``tokens``."""
    static = _static(cfg)
    n = len(tokens)
    t = -(-n // _PAD) * _PAD
    # padded on the host, so that every length of one bucket runs the same
    # few programs; the pass is causal, so what follows a position never
    # reaches it
    toks = np.zeros((t,), np.int32)
    toks[:n] = tokens
    x = _embed(w["embed"], toks, scale=float(cfg["scale_emb"]))
    pos = np.arange(t, dtype=np.int32)
    for r, (kind, ids) in enumerate(runs_of(cfg)):
        pre = f"r{r}_"
        run_w = {k[len(pre):]: v for k, v in w.items() if k.startswith(pre)}
        for i, lid in enumerate(ids):
            x = _layer(x, run_w, jnp.int32(i), pos, slopes(cfg, lid),
                       kind=kind, static=static, quant=quant)
    m = len(positions_wanted)
    want = np.zeros((-(-m // 128) * 128,), np.int32)
    want[:m] = positions_wanted
    out = _head(x, want, w["norm_f"], w["w_head"], eps=static[0],
                div=cfg["hidden_size"] / cfg["dim_model_base"], quant=quant)
    return np.asarray(out)[:m]

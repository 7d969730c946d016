"""The plain reference: Falcon-H1's forward pass (`falcon_h1`) in
straightforward `jax.numpy`, float32, matrix products at `highest`
precision, no cache, no chunked scan, no batching, no kernel. It imports
nothing of the program.

`h0 = E[token] * embedding_multiplier`. Every layer runs BOTH mixers on one
normed input and adds them (all projections bias-free):

    x   = RMSNorm(h; ln1)
    # the state-space branch (Mamba-2)
    p   = ((x * ssm_in_multiplier) @ W_in) * mup
          [z d_ssm | c d_ssm + 2 G N | dt heads], mup = ssm_multipliers[0..4]
          on the segments [z, x, B, C, dt]
    u   = silu(conv1d_depthwise(c; mamba_d_conv taps, causal, bias))
          [xc d_ssm | B G N | C G N]
    dt  = softplus(dt + dt_bias);  A = -exp(A_log)            (a head)
    S_t = exp(dt_t A) S_{t-1} + dt_t xc_t (x) B_t;  y_t = S_t C_t + D xc_t
          (the heads of a group share B and C; computed here in the exact
          quadratic form `y_t = sum_{j<=t} exp(sum_{j<i<=t} dt_i A) dt_j
          (C_t . B_j) xc_j`, in blocks of queries)
    y   = RMSNorm over each GROUP's channels (y * silu(z); norm)
    m   = (y @ W_out) * ssm_out_multiplier
    # the attention branch
    q = (x * attention_in_multiplier) @ Wq;  k = (.. @ Wk) * key_multiplier
    v = .. @ Wv;  q, k = rope(q), rope(k)   (theta, the whole head,
          rotate-half: channel i pairs with i + head_dim / 2)
    a   = (softmax(q k^T / sqrt(head_dim), causal) v) @ Wo
          * attention_out_multiplier
    h   = h + m + a
    x2  = RMSNorm(h; ln2)
    h   = h + (silu((x2 @ Wg) * mlp_multipliers[0]) * (x2 @ Wu)) @ Wd
              * mlp_multipliers[1]

logits `= (RMSNorm(h_L; norm_f) @ head) * lm_head_multiplier` (untied).

Departures from the published code (`transformers`' `modeling_falcon_h1`),
each without effect on the values: the published module folds
`ssm_in_multiplier` into `mup` and scales the embedding inside the
embedding call; it computes the scan in chunks of `mamba_chunk_size` (an
exact re-bracketing of the same sum); `mamba_expand` is not used where
`mamba_d_ssm` is given. `mamba_rms_norm` true, `mamba_norm_before_gate`
false, `projectors_bias`, `mamba_proj_bias`, `attention_bias`, `mlp_bias`
false and `mamba_conv_bias` true are the only forms written here
(`program.build` refuses another).

It runs layer by layer over the benchmark's own stacked weights so that it
fits beside them on the chip. ``quant="fp8"`` is the control: the same pass
with every matrix product's two inputs rounded to float8 (e4m3), the
nearest precision below the bf16 the configuration states.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

_QBLOCK = 256          # query rows per block of attention
_SBLOCK = 128          # and of the scan (its decays are a head each)
_PAD = 1024            # sequences are padded to a multiple of this
_HBLOCK = 256          # positions per call of the head


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


def _rms_grouped(x, scale, eps, groups: int):
    """RMSNorm over each of ``groups`` equal runs of the last axis."""
    t, d = x.shape
    xg = x.reshape(t, groups, d // groups)
    xg = xg * jax.lax.rsqrt(jnp.mean(jnp.square(xg), -1, keepdims=True)
                            + eps)
    return xg.reshape(t, d) * scale


def _rope(x, theta: float):
    """[T, H, D] at positions 0..T-1: channel i turns with channel i + D/2
    by the angle `t * theta^(-2 i / D)`."""
    t, _h, d = x.shape
    half = d // 2
    freq = theta ** (-np.arange(half, dtype=np.float64) / half)
    ang = jnp.asarray(np.arange(t, dtype=np.float64)[:, None] * freq,
                      jnp.float32)[:, None, :]               # [T, 1, half]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def _attention(q, k, v, quant):
    """[T, H, D] x [T, K, D] -> [T, H, D], causal, scale 1 / sqrt(D), in
    blocks of queries."""
    t, h, d = q.shape
    kvh = k.shape[1]
    kq, vq = _fq(k, quant), _fq(v, quant)
    cols = jnp.arange(t)

    def block(args):
        i, qi = args                                         # [Q, K, G, D]
        rows = i * _QBLOCK + jnp.arange(_QBLOCK)
        s = jnp.einsum("qkgd,tkd->kgqt", _fq(qi, quant), kq) / d ** 0.5
        s = jnp.where(cols[None, :] <= rows[:, None], s, -jnp.inf)
        return jnp.einsum("kgqt,tkd->qkgd",
                          _fq(jax.nn.softmax(s, axis=-1), quant), vq)

    out = jax.lax.map(block, (jnp.arange(t // _QBLOCK), q.reshape(
        t // _QBLOCK, _QBLOCK, kvh, h // kvh, d)))
    return out.reshape(t, h, d)


def _scan(x, delta, a, bm, cm, quant):
    """The selective scan in its quadratic form. ``x`` [T, G, E, P],
    ``delta`` [T, G, E], ``a`` [G, E], ``bm``/``cm`` [T, G, N] -> [T, G, E,
    P]."""
    t = x.shape[0]
    cum = jnp.cumsum(delta * a, axis=0)                          # [T, G, E]
    into = _fq(delta[..., None] * x, quant)                      # [T,G,E,P]
    bq = _fq(bm, quant)
    cols = jnp.arange(t)

    def block(args):
        i, ci, cum_i = args                      # [Q, G, N], [Q, G, E]
        rows = i * _SBLOCK + jnp.arange(_SBLOCK)
        seen = (cols[None, :] <= rows[:, None])[..., None, None]
        seg = cum_i[:, None] - cum[None, :]                      # [Q,T,G,E]
        decay = jnp.where(seen, jnp.exp(jnp.where(seen, seg, 0.0)), 0.0)
        s = jnp.einsum("qgn,tgn->qtg", _fq(ci, quant), bq)
        return jnp.einsum("qtge,tgep->qgep",
                          _fq(s[..., None] * decay, quant), into)

    nq = t // _SBLOCK
    out = jax.lax.map(block, (
        jnp.arange(nq), cm.reshape((nq, _SBLOCK) + cm.shape[1:]),
        cum.reshape((nq, _SBLOCK) + cum.shape[1:])))
    return out.reshape(x.shape)


def _mamba(u, at, dims, mults, eps, quant):
    """The state-space branch over its (already multiplied) input ``u``
    [T, hidden], before `ssm_out_multiplier`."""
    heads, p, n, g, taps = dims
    inner = heads * p
    t = u.shape[0]
    zcd = (_fq(u, quant) @ _fq(at("w_in"), quant, 0)) * jnp.asarray(
        np.repeat(np.asarray(mults, np.float32),
                  (inner, inner, g * n, g * n, heads)))
    z, c, dt = jnp.split(zcd, (inner, 2 * inner + 2 * g * n), axis=-1)
    padded = jnp.pad(c, ((taps - 1, 0), (0, 0)))
    w_conv = at("conv_w")                                   # [taps, width]
    c = jax.nn.silu(at("conv_b") + sum(
        padded[j:j + t] * w_conv[j] for j in range(taps)))
    x = c[:, :inner].reshape(t, g, heads // g, p)
    bm = c[:, inner:inner + g * n].reshape(t, g, n)
    cm = c[:, inner + g * n:].reshape(t, g, n)
    delta = jax.nn.softplus(dt + at("dt_bias")).reshape(t, g, heads // g)
    a = -jnp.exp(at("A_log")).reshape(g, heads // g)
    y = _scan(x, delta, a, bm, cm, quant)
    y = y + at("D").reshape(g, heads // g)[..., None] * x
    y = _rms_grouped(y.reshape(t, inner) * jax.nn.silu(z), at("norm"), eps,
                     g)
    return _fq(y, quant) @ _fq(at("w_out"), quant, 0)


@partial(jax.jit, static_argnames=("static", "quant"))
def _layer(h, w, l, *, static, quant):
    (eps, theta, key_mult, att_in, att_out, ssm_in, ssm_out, mults,
     mlp_mults, dims) = static

    def at(name):
        return jax.lax.dynamic_index_in_dim(
            w[name], l, 0, keepdims=False).astype(jnp.float32)

    with jax.default_matmul_precision("highest"):
        x = _rms(h, at("ln1"), eps)
        m = _mamba(x * ssm_in, at, dims, mults, eps, quant) * ssm_out
        xa = _fq(x * att_in, quant)
        q, k, v = (jnp.einsum("td,dhk->thk", xa, _fq(at(n), quant, 0))
                   for n in ("wq", "wk", "wv"))
        o = _attention(_rope(q, theta), _rope(k * key_mult, theta), v, quant)
        wo = at("wo")
        a = (_fq(o.reshape(o.shape[0], -1), quant)
             @ _fq(wo.reshape(-1, wo.shape[-1]), quant, 0)) * att_out
        h = h + m + a
        x2 = _fq(_rms(h, at("ln2"), eps), quant)
        gate = (x2 @ _fq(at("wg"), quant, 0)) * mlp_mults[0]
        up = x2 @ _fq(at("wu"), quant, 0)
        return h + (_fq(jax.nn.silu(gate) * up, quant)
                    @ _fq(at("wd"), quant, 0)) * mlp_mults[1]


@partial(jax.jit, static_argnames=("scale",))
def _embed(table, toks, *, scale):
    return table[toks].astype(jnp.float32) * scale


@partial(jax.jit, static_argnames=("eps", "mult", "quant"))
def _head(x, want, norm, head, *, eps, mult, quant):
    """Logits of the rows ``want`` of ``x``: the head a block of columns at
    a time (whole in float32 it would be 5.3 GB at the published size)."""
    v = head.shape[1]
    nb = next(n for n in (8, 4, 2, 1) if v % n == 0)
    with jax.default_matmul_precision("highest"):
        hn = _fq(_rms(x[want], norm.astype(jnp.float32), eps), quant)

        def block(j):
            cols = jax.lax.dynamic_slice_in_dim(head, j * (v // nb), v // nb,
                                                axis=1)
            return hn @ _fq(cols.astype(jnp.float32), quant, 0)

        out = jax.lax.map(block, jnp.arange(nb))             # [nb, m, v/nb]
        return jnp.moveaxis(out, 0, 1).reshape(hn.shape[0], v) * mult


def _static(cfg: dict) -> tuple:
    return (float(cfg["rms_norm_eps"]), float(cfg["rope_theta"]),
            float(cfg["key_multiplier"]),
            float(cfg["attention_in_multiplier"]),
            float(cfg["attention_out_multiplier"]),
            float(cfg["ssm_in_multiplier"]),
            float(cfg["ssm_out_multiplier"]),
            tuple(float(m) for m in cfg["ssm_multipliers"]),
            tuple(float(m) for m in cfg["mlp_multipliers"]),
            (cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"],
             cfg["mamba_n_groups"], cfg["mamba_d_conv"]))


_LAYER = ("ln1", "w_in", "conv_w", "conv_b", "dt_bias", "A_log", "D", "norm",
          "w_out", "wq", "wk", "wv", "wo", "ln2", "wg", "wu", "wd")


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
    h = _embed(w["embed"], toks, scale=float(cfg["embedding_multiplier"]))
    stack = {k: w[k] for k in _LAYER}
    for i in range(cfg["num_hidden_layers"]):
        h = _layer(h, stack, jnp.int32(i), static=static, quant=quant)
    # the head a block of positions at a time: 1536 rows of 261120 float32
    # logits are 1.6 GB, which the host holds and the device need not
    out = []
    for o in range(0, len(positions_wanted), _HBLOCK):
        want = np.zeros((_HBLOCK,), np.int32)
        part = positions_wanted[o:o + _HBLOCK]
        want[:len(part)] = part
        out.append(np.asarray(_head(
            h, want, w["norm_f"], w["head"], eps=static[0],
            mult=float(cfg["lm_head_multiplier"]), quant=quant))[:len(part)])
    return np.concatenate(out)

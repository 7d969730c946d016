"""The plain reference: granite-4.0-h's forward pass (`granitemoehybrid`) in
straightforward `jax.numpy`, float32, matrix products at `highest`
precision, no cache, no chunked scan, no batching, no grouping of tokens, no
kernel. It imports nothing of the program.

`x0 = E[token] * embedding_multiplier`; a layer is `x += r * Mixer(RMSNorm
(x)); x += r * (Routed(u) + Shared(u))`, `u = RMSNorm(x)`, `r =
residual_multiplier`; logits `= RMSNorm(x) @ E^T / logits_scaling` (tied).

* ``attention``: grouped-query causal softmax attention, NO positional
  embedding, scores times `attention_multiplier`, no bias;
* ``mamba`` (Mamba-2): `[z, c, dt] = u W_in`; a depthwise causal convolution
  of `mamba_d_conv` taps over `c` (zeros before the first token), plus
  bias, SiLU; `[x, B, C]` split from it; `delta = softplus(dt + dt_bias)`,
  `A = -exp(A_log)`; `S_t = exp(delta_t A) S_{t-1} + delta_t x_t (x) B_t`,
  `y_t = S_t C_t + D x_t`, computed here in its exact quadratic form `y_t =
  sum_{j<=t} exp(sum_{j<i<=t} delta_i A) delta_j (C_t . B_j) x_j`, in blocks
  of queries; `RMSNorm(y * silu(z)) W_out` (the norm over all of
  `d_inner`: one group);
* ``Routed``: router logits over ALL the experts, the `num_experts_per_tok`
  largest, a softmax over those alone, `sum gate_e * expert_e(u)` with
  `expert_e(u) = (silu(h[:f]) * h[f:]) W2_e`, `h = u W1_e`: every token
  through every HELD expert, one expert at a time, weighted by its gate
  (zero where not chosen). The configuration holds the first
  `num_local_experts` of the router's `published.num_local_experts`: the
  others' terms are left out, as on the chip that this one stands for;
* ``Shared``: the same gated form, every token.

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

_QBLOCK = 256          # query rows per block of attention
_SBLOCK = 128          # and of the scan (its decays are a head each)
_PAD = 1024            # sequences are padded to a multiple of this


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


def _gated(u, w1, w2, quant):
    """`(silu(h[:f]) * h[f:]) W2`, `h = u W1`; ``u`` already rounded."""
    f = w2.shape[0]
    h = u @ _fq(w1, quant, 0)
    return _fq(jax.nn.silu(h[:, :f]) * h[:, f:], quant) @ _fq(w2, quant, 0)


def _attention(q, k, v, scale, quant):
    """[T, H, D] x [T, K, D] -> [T, H, D], causal, in blocks of queries."""
    t, h, d = q.shape
    kvh = k.shape[1]
    kq, vq = _fq(k, quant), _fq(v, quant)
    cols = jnp.arange(t)

    def block(args):
        i, qi = args                                         # [Q, K, G, D]
        rows = i * _QBLOCK + jnp.arange(_QBLOCK)
        s = jnp.einsum("qkgd,tkd->kgqt", _fq(qi, quant), kq) * scale
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


def _mamba(u, at, dims, eps, quant):
    heads, p, n, g, taps = dims
    inner = heads * p
    t = u.shape[0]
    zcd = u @ _fq(at("w_in"), quant, 0)
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
    y = _rms(y.reshape(t, inner) * jax.nn.silu(z), at("norm"), eps)
    return _fq(y, quant) @ _fq(at("w_out"), quant, 0)


def _routed(u, uq, at, top_k, first, quant):
    """``u`` the normed tokens (the router reads them as they are: it runs
    in float32 under every precision), ``uq`` the same rounded."""
    logits = u @ at("router")                                # [T, E]
    top, idx = jax.lax.top_k(logits, top_k)
    gates = jnp.zeros_like(logits).at[
        jnp.arange(u.shape[0])[:, None], idx].set(jax.nn.softmax(top, -1))
    w1, w2 = at("w1"), at("w2")                     # the held experts'

    def one(acc, e):
        y = _gated(uq, w1[e], w2[e], quant)
        return acc + gates[:, first + e, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(u), jnp.arange(w1.shape[0]))
    return out


@partial(jax.jit, static_argnames=("kind", "static", "quant"))
def _layer(x, w, l, *, kind, static, quant):
    eps, r, scale, top_k, first, dims = static

    def at(name):
        return jax.lax.dynamic_index_in_dim(
            w[name], l, 0, keepdims=False).astype(jnp.float32)

    with jax.default_matmul_precision("highest"):
        u = _fq(_rms(x, at("ln1"), eps), quant)
        if kind == "mamba":
            mix = _mamba(u, at, dims, eps, quant)
        else:
            q, k, v = (jnp.einsum("td,dhk->thk", u, _fq(at(n), quant, 0))
                       for n in ("wq", "wk", "wv"))
            o = _attention(q, k, v, scale, quant)
            wo = at("wo")
            mix = (_fq(o.reshape(o.shape[0], -1), quant)
                   @ _fq(wo.reshape(-1, wo.shape[-1]), quant, 0))
        x = x + r * mix
        u = _rms(x, at("ln2"), eps)
        uq = _fq(u, quant)
        return x + r * (_routed(u, uq, at, top_k, first, quant)
                        + _gated(uq, at("ws1"), at("ws2"), quant))


@partial(jax.jit, static_argnames=("scale",))
def _embed(table, toks, *, scale):
    return table[toks].astype(jnp.float32) * scale


@partial(jax.jit, static_argnames=("eps", "div", "quant"))
def _head(x, want, norm, table, *, eps, div, quant):
    with jax.default_matmul_precision("highest"):
        hn = _rms(x[want], norm.astype(jnp.float32), eps)
        return _fq(hn, quant) @ _fq(table.astype(jnp.float32), quant).T / div


def runs_of(cfg: dict) -> list[tuple[str, int]]:
    """Consecutive layers of one kind: [(kind, how many)]. The weights are
    stacked a run at a time under `r<i>_<name>`."""
    out: list[list] = []
    for kind in cfg["layer_types"]:
        if out and out[-1][0] == kind:
            out[-1][1] += 1
        else:
            out.append([kind, 1])
    return [(k, n) for k, n in out]


def _static(cfg: dict) -> tuple:
    return (float(cfg["rms_norm_eps"]), float(cfg["residual_multiplier"]),
            float(cfg["attention_multiplier"]),
            int(cfg["num_experts_per_tok"]),
            int(cfg.get("first_local_expert", 0)),
            (cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"],
             cfg["mamba_n_groups"], cfg["mamba_d_conv"]))


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
    x = _embed(w["embed"], toks, scale=float(cfg["embedding_multiplier"]))
    for r, (kind, layers) in enumerate(runs_of(cfg)):
        pre = f"r{r}_"
        run_w = {k[len(pre):]: v for k, v in w.items() if k.startswith(pre)}
        for i in range(layers):
            x = _layer(x, run_w, jnp.int32(i), kind=kind, static=static,
                       quant=quant)
    m = len(positions_wanted)
    want = np.zeros((-(-m // 128) * 128,), np.int32)
    want[:m] = positions_wanted
    out = _head(x, want, w["norm_f"], w["embed"], eps=static[0],
                div=float(cfg["logits_scaling"]), quant=quant)
    return np.asarray(out)[:m]

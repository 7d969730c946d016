"""The plain reference: Qwen3-Next's forward pass (`qwen3_next`) in
straightforward `jax.numpy`, float32, matrix products at `highest`
precision, no cache, no chunked form, no batching, no grouping of tokens, no
kernel. It imports nothing of the program.

`h0 = E[token]`; a layer is `h += Mixer(N(h)); h += MoE(N(h))`, `N(x) = x /
rms(x) * s` (Qwen3NextRMSNorm, float32, eps `rms_norm_eps`; the published
`1 + w` is stored as `s`); logits `= N(h_L) @ head` (untied).

* ``linear_attention`` (Gated DeltaNet; key heads KH, value heads VH = r KH,
  widths dk and dv): `[q k v z] = u W_qkvz`, `[b a] = u W_ba`, grouped by
  key head as published (a key head's `[q dk | k dk | v r dv | z r dv]`
  and `[b r | a r]`); `c = silu(conv1d_depthwise([q | k | v]))`, causal,
  `linear_conv_kernel_dim` taps, no bias (zeros before the first token);
  `q^ = l2norm(q) / sqrt(dk)`, `k^ = l2norm(k)` (eps 1e-6), value head j
  reading key head j // r; `beta = sigmoid(b)`, `g = -exp(A_log)
  softplus(a + dt_bias)`; then a TOKEN AT A TIME, a state S [dk, dv] a
  value head from zeros: `S = exp(g_t) S; S = S + k^_t (beta_t (v_t -
  S^T k^_t))^T; o_t = S^T q^_t`; `o = RMSNorm(o) * norm * silu(z)` a head
  over dv (Qwen3NextRMSNormGated: the norm, then the gate); `o W_out`;
* ``full_attention`` (gated attention): `[q | gate] = u W_q` a head, `q =
  N(q; qn)`, `k = N(u W_k; kn)` a head, rotate-half rotary positions over
  the first `head_dim x partial_rotary_factor` channels at `rope_theta`,
  causal softmax at 1 / sqrt(head_dim), grouped-query, `o * sigmoid(gate)`,
  `W_o`;
* ``MoE``: `p = softmax(u W_r)` over ALL the router's experts, the
  `num_experts_per_tok` largest, divided by their sum (`norm_topk_prob`);
  `sum p_e expert_e(u)` over the chosen experts HELD here (the first
  `num_experts` of the published count: the others' terms are left out, as
  on the chip that this one stands for), `expert_e(u) = (silu(h[:f]) *
  h[f:]) W2_e`, `h = u W1_e`, every token through every held expert, one
  expert at a time, weighted by its gate (zero where not chosen); plus
  `sigmoid(u w_sg) Shared(u)`, the shared expert of the same form.

It runs layer by layer over the benchmark's own stacked weights so that it
fits beside them on the chip. ``quant="fp8"`` is the control: the same pass
with every matrix product's two inputs rounded to float8 (e4m3), the nearest
precision below the bf16 the configuration states; the router's logits and
the recurrence stay float32 under it, as the configuration runs them.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

_QBLOCK = 256          # query rows per block of attention
_PAD = 4096            # sequences are padded to a multiple of this,
_PAD_SHORT = 256       # or of this where they are no longer than _PAD
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


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True)
                             + 1e-6)


def _gated(u, w1, w2, quant):
    """`(silu(h[:f]) * h[f:]) W2`, `h = u W1`; ``u`` already rounded."""
    f = w2.shape[0]
    h = u @ _fq(w1, quant, 0)
    return _fq(jax.nn.silu(h[:, :f]) * h[:, f:], quant) @ _fq(w2, quant, 0)


def _rope(x, theta: float, dims: int):
    """[T, H, D] at positions 0..T-1: of the first ``dims`` channels,
    channel i turns with channel i + dims/2 by the angle `t * theta^(-2 i /
    dims)`; the rest pass."""
    t = x.shape[0]
    half = dims // 2
    freq = theta ** (-np.arange(half, dtype=np.float64) / half)
    ang = jnp.asarray(np.arange(t, dtype=np.float64)[:, None] * freq,
                      jnp.float32)[:, None, :]               # [T, 1, half]
    x1, x2, rest = x[..., :half], x[..., half:dims], x[..., dims:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang), rest], -1)


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


def _delta_rule(q, k, v, g, beta):
    """The gated delta rule a token at a time: ``q``/``k`` [T, H, dk],
    ``v`` [T, H, dv], ``g``/``beta`` [T, H] -> o [T, H, dv]."""
    def step(s, x):
        qt, kt, vt, gt, bt = x
        s = s * jnp.exp(gt)[:, None, None]
        err = vt - jnp.einsum("hd,hde->he", kt, s)
        s = s + jnp.einsum("hd,he->hde", kt, bt[:, None] * err)
        return s, jnp.einsum("hd,hde->he", qt, s)

    h, dk, dv = q.shape[1], q.shape[2], v.shape[2]
    _s, o = jax.lax.scan(step, jnp.zeros((h, dk, dv), jnp.float32),
                         (q, k, v, g, beta))
    return o


def _gated_deltanet(u, at, dims, eps, quant):
    kh, vh, dk, dv, taps = dims
    r = vh // kh
    t = u.shape[0]
    uq = _fq(u, quant)
    qkvz = jnp.einsum("td,dkc->tkc", uq, _fq(at("w_qkvz"), quant, 0))
    ba = jnp.einsum("td,dkc->tkc", uq, _fq(at("w_ba"), quant, 0))
    q, k, v, z = jnp.split(qkvz, (dk, 2 * dk, 2 * dk + r * dv), axis=-1)
    mixed = jnp.concatenate([x.reshape(t, -1) for x in (q, k, v)], axis=-1)
    padded = jnp.pad(mixed, ((taps - 1, 0), (0, 0)))
    w_conv = at("conv_w")                                   # [taps, width]
    c = jax.nn.silu(sum(padded[j:j + t] * w_conv[j] for j in range(taps)))
    q, k, v = jnp.split(c, (kh * dk, 2 * kh * dk), axis=-1)
    q = jnp.repeat(_l2norm(q.reshape(t, kh, dk)) / dk ** 0.5, r, axis=1)
    k = jnp.repeat(_l2norm(k.reshape(t, kh, dk)), r, axis=1)
    beta = jax.nn.sigmoid(ba[..., :r].reshape(t, vh))
    g = -jnp.exp(at("A_log")) * jax.nn.softplus(ba[..., r:].reshape(t, vh)
                                                + at("dt_bias"))
    o = _delta_rule(q, k, v.reshape(t, vh, dv), g, beta)
    o = _rms(o, at("norm"), eps) * jax.nn.silu(z.reshape(t, vh, dv))
    w_out = at("w_out")
    return (_fq(o.reshape(t, -1), quant)
            @ _fq(w_out.reshape(-1, w_out.shape[-1]), quant, 0))


def _gated_attention(u, at, theta, rot, eps, quant):
    uq = _fq(u, quant)
    hd = at("wk").shape[-1]
    qg = jnp.einsum("td,dhk->thk", uq, _fq(at("wq"), quant, 0))
    q, gate = qg[..., :hd], qg[..., hd:]
    k, v = (jnp.einsum("td,dhk->thk", uq, _fq(at(n), quant, 0))
            for n in ("wk", "wv"))
    q = _rope(_rms(q, at("qn"), eps), theta, rot)
    k = _rope(_rms(k, at("kn"), eps), theta, rot)
    o = _attention(q, k, v, quant) * jax.nn.sigmoid(gate)
    wo = at("wo")
    return (_fq(o.reshape(o.shape[0], -1), quant)
            @ _fq(wo.reshape(-1, wo.shape[-1]), quant, 0))


def _routed(u, uq, at, top_k, first, quant):
    """``u`` the normed tokens (the router reads them as they are: it runs
    in float32 under every precision), ``uq`` the same rounded."""
    probs = jax.nn.softmax(u @ at("router"), axis=-1)         # [T, E]
    top, idx = jax.lax.top_k(probs, top_k)
    gates = jnp.zeros_like(probs).at[
        jnp.arange(u.shape[0])[:, None], idx].set(
            top / jnp.sum(top, axis=-1, keepdims=True))
    w1, w2 = at("w1"), at("w2")                     # the held experts'

    def one(acc, e):
        # an expert at a time in float32: the layer's 256 whole would be
        # 3.2 GB beside the weights
        y = _gated(uq, w1[e].astype(jnp.float32), w2[e].astype(jnp.float32),
                   quant)
        return acc + gates[:, first + e, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(u), jnp.arange(w1.shape[0]))
    return out


@partial(jax.jit, static_argnames=("kind", "static", "quant"))
def _layer(h, w, l, *, kind, static, quant):
    eps, theta, rot, top_k, first, dims = static

    def at(name):
        x = jax.lax.dynamic_index_in_dim(w[name], l, 0, keepdims=False)
        return x if name in ("w1", "w2") else x.astype(jnp.float32)

    with jax.default_matmul_precision("highest"):
        u = _rms(h, at("ln1"), eps)
        if kind == "linear_attention":
            h = h + _gated_deltanet(u, at, dims, eps, quant)
        else:
            h = h + _gated_attention(u, at, theta, rot, eps, quant)
        u = _rms(h, at("ln2"), eps)
        uq = _fq(u, quant)
        shared = (jax.nn.sigmoid(u @ at("wsg"))
                  * _gated(uq, at("ws1"), at("ws2"), quant))
        return h + _routed(u, uq, at, top_k, first, quant) + shared


@jax.jit
def _embed(table, toks):
    return table[toks].astype(jnp.float32)


@partial(jax.jit, static_argnames=("eps", "quant"))
def _head(h, want, norm, head, *, eps, quant):
    """Logits of the rows ``want`` of ``h``: the head a block of columns at
    a time (whole in float32 it would be 1.2 GB at the published size)."""
    v = head.shape[1]
    nb = next(n for n in (8, 4, 2, 1) if v % n == 0)
    with jax.default_matmul_precision("highest"):
        hn = _fq(_rms(h[want], norm.astype(jnp.float32), eps), quant)

        def block(j):
            cols = jax.lax.dynamic_slice_in_dim(head, j * (v // nb), v // nb,
                                                axis=1)
            return hn @ _fq(cols.astype(jnp.float32), quant, 0)

        out = jax.lax.map(block, jnp.arange(nb))             # [nb, m, v/nb]
        return jnp.moveaxis(out, 0, 1).reshape(hn.shape[0], v)


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
    return (float(cfg["rms_norm_eps"]), float(cfg["rope_theta"]),
            int(cfg["head_dim"] * cfg["partial_rotary_factor"]),
            int(cfg["num_experts_per_tok"]),
            int(cfg.get("first_local_expert", 0)),
            (cfg["linear_num_key_heads"], cfg["linear_num_value_heads"],
             cfg["linear_key_head_dim"], cfg["linear_value_head_dim"],
             cfg["linear_conv_kernel_dim"]))


def logits_at(w: dict, cfg: dict, tokens, positions_wanted,
              quant: str | None = None):
    """Float32 logits of the reference at ``positions_wanted`` (indices into
    ``tokens``) after one full forward pass over ``tokens``."""
    static = _static(cfg)
    n = len(tokens)
    pad = _PAD if n > _PAD else _PAD_SHORT
    t = -(-n // pad) * pad
    # padded on the host, so that the cell's lengths run a few programs
    # (8192..36864: eight); the pass is causal, so what follows a position
    # never reaches it
    toks = np.zeros((t,), np.int32)
    toks[:n] = tokens
    h = _embed(w["embed"], toks)
    for r, (kind, layers) in enumerate(runs_of(cfg)):
        pre = f"r{r}_"
        run_w = {k[len(pre):]: v for k, v in w.items() if k.startswith(pre)}
        for i in range(layers):
            h = _layer(h, run_w, jnp.int32(i), kind=kind, static=static,
                       quant=quant)
    out = []
    for o in range(0, len(positions_wanted), _HBLOCK):
        want = np.zeros((_HBLOCK,), np.int32)
        part = positions_wanted[o:o + _HBLOCK]
        want[:len(part)] = part
        out.append(np.asarray(_head(h, want, w["norm_f"], w["head"],
                                    eps=static[0], quant=quant))[:len(part)])
    return np.concatenate(out)

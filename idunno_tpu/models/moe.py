"""Mixture-of-experts model family (switch-routed FFN).

The reference's only multi-model mechanism is two whole-model jobs
fair-sharing workers (`mp4_machinelearning.py:501-539`); it has no
conditional computation. This adds a switch-style MoE FFN as a first-class
model family: a learned router picks the top-k experts per token (k=1 the
Switch layer, k=2 the GShard configuration), and the
expert FFNs either all live on every device (``mesh=None``, the dense path
— also the exact ground truth for tests) or are sharded over a mesh axis
with all_to_all dispatch (`idunno_tpu.parallel.expert`).

``MoETransformerLM`` is `idunno_tpu.models.transformer.TransformerLM` with
the switch FFN plugged in via ``ffn_factory`` — by default on every block;
``moe_every=2`` gives the Switch-Transformer every-other-block layout. It
therefore composes with ring / Ulysses sequence parallelism for free.

Training: top-1 routing collapses without pressure toward balance, so the
layer sows the Switch-Transformer auxiliary load-balancing loss
(E · Σ_e frac_routed_e · mean_prob_e) into the ``"losses"`` collection;
``moe_aux_loss`` sums it for adding to the task loss.

`routed_experts` is the serving-side layer of a stack that holds one
chip's share of a wider router (`models/hybrid.py`'s ``moe`` feed-forward
kind): dropless top-k over the whole router, gates over the chosen, only
the held experts' terms computed, tokens grouped by expert.
"""
from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from idunno_tpu.parallel.expert import (
    EXPERT_AXIS, expert_parallel_apply, switch_dispatch)
from idunno_tpu.models.transformer import AttnFn, TransformerLM
from idunno_tpu.parallel.ring_attention import full_attention


class SwitchFFN(nn.Module):
    """Top-k routed expert FFN. Input/output [B, T, dim].

    ``k=1`` is the Switch-Transformer layer (gate = raw top prob); ``k>1``
    is GShard-style top-k routing: each token is sent to its k best experts
    with gates renormalised over the chosen k. Routing-to-dispatch reuses
    the top-1 machinery by treating each (token, choice) pair as its own
    routing unit — capacity then naturally accounts for all k streams."""

    dim: int
    hidden: int
    n_experts: int
    k: int = 1
    capacity_factor: float = 2.0
    mesh: Mesh | None = None            # None → dense (all experts local)
    axis: str = EXPERT_AXIS
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    def _expert_params(self):
        e, d, h = self.n_experts, self.dim, self.hidden
        init = nn.initializers.lecun_normal()
        return {
            "w1": self.param("w1", init, (e, d, h), self.param_dtype),
            "b1": self.param("b1", nn.initializers.zeros, (e, h),
                             self.param_dtype),
            "w2": self.param("w2", init, (e, h, d), self.param_dtype),
            "b2": self.param("b2", nn.initializers.zeros, (e, d),
                             self.param_dtype),
        }

    @nn.compact
    def __call__(self, x):
        b, t, d = x.shape
        n = b * t
        router = nn.Dense(self.n_experts, dtype=jnp.float32,
                          param_dtype=self.param_dtype, name="router")
        if not 1 <= self.k <= self.n_experts:
            raise ValueError(f"k={self.k}: want 1..{self.n_experts}")
        probs = jax.nn.softmax(router(x.astype(jnp.float32)).reshape(
            n, self.n_experts))
        topk_w, topk_idx = jax.lax.top_k(probs, self.k)        # [n, k]
        if self.k == 1:
            gate_idx, gate_w = topk_idx[:, 0], topk_w[:, 0]    # switch
        else:
            # GShard top-k: renormalise the chosen gates; flatten so every
            # (token, choice) is one routing unit in dispatch order
            # [t0c0, t0c1, ..., t1c0, ...] (stays aligned with
            # jnp.repeat(flat, k) below and with contiguous token sharding).
            topk_w = topk_w / topk_w.sum(axis=-1, keepdims=True)
            gate_idx, gate_w = topk_idx.reshape(-1), topk_w.reshape(-1)

        # Switch-Transformer load-balance loss: E · Σ_e f_e · P_e with f_e
        # the top-1 routing fraction, minimized (=1) at uniform routing.
        # Without it routing collapses onto one expert and capacity drops
        # kill most tokens' FFN output.
        frac = jax.nn.one_hot(topk_idx[:, 0], self.n_experts).mean(axis=0)
        aux = self.n_experts * jnp.sum(frac * probs.mean(axis=0))
        self.sow("losses", "moe_aux", aux)

        params = self._expert_params()
        flat = x.reshape(n, d)
        if self.k > 1:
            flat = jnp.repeat(flat, self.k, axis=0)            # [n*k, d]
        n_units = n * self.k

        def expert_fn(p, toks):
            h = jnp.einsum("cd,dh->ch", toks.astype(self.dtype),
                           p["w1"].astype(self.dtype)) + p["b1"]
            return (jnp.einsum("ch,hd->cd", nn.gelu(h),
                               p["w2"].astype(self.dtype))
                    + p["b2"]).astype(jnp.float32)

        if self.mesh is not None:
            p_sz = self.mesh.shape[self.axis]
            cap = self._capacity(n_units // p_sz)
            out = expert_parallel_apply(expert_fn, params, flat, gate_idx,
                                        gate_w, self.mesh, axis=self.axis,
                                        capacity=cap)
        else:
            dispatch, combine = switch_dispatch(
                gate_idx, gate_w, self.n_experts, self._capacity(n_units))
            buf = jnp.einsum("nec,nd->ecd", dispatch, flat)
            done = jax.vmap(expert_fn)(params, buf)
            out = jnp.einsum("ecd,nec->nd", done, combine)
        if self.k > 1:
            out = out.reshape(n, self.k, d).sum(axis=1)        # combine k
        return out.reshape(b, t, d).astype(x.dtype)

    def _capacity(self, tokens_per_shard: int) -> int:
        # floor at k: one token's k choices can all land on one expert, and
        # for tiny token counts (single-token decode steps) the proportional
        # capacity would otherwise guarantee dropped streams
        return max(self.k, int(self.capacity_factor * tokens_per_shard
                               / self.n_experts))


def routed_experts(x, router, w1, w2, *, top_k: int,
                   experts_held: tuple[int, int], mask=None,
                   dense: bool = False, layer=None):
    """One chip's share of a dropless top-k expert layer.

    ``x`` [T, d] tokens, ``router`` [d, E] over ALL ``E`` experts, ``w1``
    [held, d, 2f] and ``w2`` [held, f, d] the gated SiLU experts
    ``first .. first + held`` (``experts_held`` = (first, held)). Every
    token takes the ``top_k`` largest of its ``E`` router logits (float32)
    and a softmax over those ``top_k`` alone, held here or not; the result
    is ``sum gate_e * expert_e(x)`` over the chosen experts THAT ARE HELD:
    what the other chips of the layer would add is left out, and nothing
    stands in for them. No capacity, no token dropped. Tokens where
    ``mask`` [T] is False (padding, a dead row) are routed nowhere. With
    ``layer`` (an index, traced or not) ``w1`` and ``w2`` are a run of
    layers' experts stacked, [L, held, ...], and the layer's are taken
    where they lie: the grouped product is handed the whole stack as
    L x held groups of which only this layer's hold rows, because a slice
    of the stack handed to it would be copied first (0.68 GB a layer at
    granite-4.0-h-small's widths).

    Grouped (the default): the (token, pick) pairs are sorted by expert and
    go through two `jax.lax.ragged_dot`s, so the work is that of the picks
    that fell here, whatever their spread over the experts. ``dense``
    multiplies every token by every held expert and weights by the gates
    (zero where not picked): `held / picks-here` times the operations, one
    batched product, and every held expert's weights read: for a handful
    of rows whose picks touch nearly every held expert anyway (granite's 33
    live rows x 10 of 72). Where they touch few of them (Qwen3-Next's 16 x
    10 of 512: some 69 of 256 held) it reads 4-7 times the bytes the picks
    need (`models/hybrid.py:reads_every_held_expert` chooses).

    Returns (y [T, d] in ``x``'s type, load [held] int32: the picks each
    held expert took). This is what an expert-parallel mesh calls a chip
    (`idunno_tpu.parallel.expert`), between its two exchanges."""
    t, d = x.shape
    first, held = experts_held
    f = w2.shape[-2]
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                        router.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    top, idx = jax.lax.top_k(logits, top_k)                      # [T, k]
    gates = jax.nn.softmax(top, axis=-1)
    here = (idx >= first) & (idx < first + held)
    if mask is not None:
        here = here & mask[:, None]
    local = jnp.where(here, idx - first, held)       # `held`: not computed
    # comparisons and sorts, no scatter: the chip scatters an element at a
    # time
    picked = local[..., None] == jnp.arange(held)            # [T, k, held]
    load = jnp.sum(picked, axis=(0, 1), dtype=jnp.int32)

    def gated(h):
        return jax.nn.silu(h[..., :f]) * h[..., f:]

    sizes = load
    if layer is not None and dense:
        w1, w2 = (jax.lax.dynamic_index_in_dim(w, layer, 0, keepdims=False)
                  for w in (w1, w2))
    elif layer is not None:
        sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((w1.shape[0], held), jnp.int32), load[None],
            (layer, 0)).reshape(-1)
        w1, w2 = (w.reshape((-1,) + w.shape[2:]) for w in (w1, w2))
    if dense:
        g = jnp.sum(jnp.where(picked, gates[..., None], 0.0), axis=1)
        h = gated(jnp.einsum("td,edf->etf", x, w1)) * g.T.astype(
            x.dtype)[..., None]
        return jnp.einsum("etf,efd->td", h, w2), load
    order = jnp.argsort(local.reshape(-1), stable=True)          # [T k]
    back = jnp.argsort(order)
    rows = jnp.take(x, order // top_k, axis=0)
    out = jax.lax.ragged_dot(gated(jax.lax.ragged_dot(rows, w1, sizes)),
                             w2, sizes)
    # back in (token, pick) order; a pick that fell on no held expert sits
    # past the last group: whatever the grouped product left in its row is
    # dropped, not weighted
    out = jnp.take(out, back, axis=0).reshape(t, top_k, d)
    y = jnp.sum(jnp.where(here[..., None], out.astype(jnp.float32)
                          * gates[..., None], 0.0), axis=1)
    return y.astype(x.dtype), load


def switch_ffn_factory(n_experts: int, capacity_factor: float = 2.0,
                       mesh: Mesh | None = None, axis: str = EXPERT_AXIS,
                       hidden_ratio: int = 4, k: int = 1):
    """An ``ffn_factory`` for `Block`/`TransformerLM` that builds a
    SwitchFFN in place of the dense MLP."""
    def make(dim: int, dtype, param_dtype, name: str) -> nn.Module:
        return SwitchFFN(dim=dim, hidden=dim * hidden_ratio,
                         n_experts=n_experts, k=k,
                         capacity_factor=capacity_factor, mesh=mesh,
                         axis=axis, dtype=dtype, param_dtype=param_dtype,
                         name=name)
    # declarative twin of this factory so `engine.generate.save_lm` can
    # persist MoE architectures: everything here is data; the mesh is CODE
    # and deliberately absent — loaders reconstruct dense (mesh=None) and
    # re-apply expert parallelism themselves if they want it
    make.lm_store_ffn = {"kind": "switch", "n_experts": n_experts,
                         "capacity_factor": capacity_factor,
                         "hidden_ratio": hidden_ratio, "k": k}
    return make


def MoETransformerLM(vocab: int = 1024, dim: int = 128, depth: int = 2,
                     num_heads: int = 4, n_experts: int = 4,
                     capacity_factor: float = 2.0, causal: bool = True,
                     attn_fn: AttnFn = full_attention,
                     mesh: Mesh | None = None, axis: str = EXPERT_AXIS,
                     moe_every: int = 1, hidden_ratio: int = 4, k: int = 1,
                     remat: bool = False,
                     dtype=jnp.float32, param_dtype=jnp.float32
                     ) -> TransformerLM:
    """Causal LM with switch-MoE FFNs — `TransformerLM` with the expert
    layer plugged in every ``moe_every``-th block (1 = all blocks, 2 = the
    Switch-Transformer interleave); ``k`` routes each token to its top-k
    experts (GShard top-2 when k=2)."""
    return TransformerLM(
        vocab=vocab, dim=dim, depth=depth, num_heads=num_heads,
        causal=causal, attn_fn=attn_fn,
        ffn_factory=switch_ffn_factory(n_experts, capacity_factor, mesh,
                                       axis, hidden_ratio, k=k),
        ffn_every=moe_every, remat=remat,
        dtype=dtype, param_dtype=param_dtype)


def moe_aux_loss(mutated_collections) -> jnp.ndarray:
    """Sum every sowed ``moe_aux`` entry (one per MoE block): call
    ``apply(..., mutable=["losses"])`` and feed the returned collections."""
    losses = mutated_collections.get("losses", {})
    return sum(jnp.sum(jnp.asarray(leaf))
               for leaf in jax.tree.leaves(losses)) if losses else jnp.asarray(0.0)

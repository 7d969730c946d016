"""Scanned fused decode (`models.transformer.scanned_apply` and friends).

Token-exactness of the scanned serving pool is pinned against
`engine.generate` in tests/test_serve_lm.py; this file holds the CPU-side
structural proxies for the perf claim the real chip has to confirm:

  - the jaxpr of one scanned decode step has a DEPTH-INVARIANT top-level
    equation count (the layer loop collapsed into one `lax.scan` body),
    strictly below the unscanned twin's, which grows linearly with depth
    — the op-count analog of "one fusion group instead of `depth`";
  - the stacked param layout round-trips quantized trees exactly;
  - the slot-curve blessing rule (`utils/lm_bench.bless_slots`) picks the
    knee, not the max.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from idunno_tpu.engine.generate import decode_model, init_cache
from idunno_tpu.models import transformer
from idunno_tpu.models.transformer import (TransformerLM, context_rungs,
                                           decode_apply, scan_compatible,
                                           stack_block_params)
from idunno_tpu.ops.quantize import dequantize_tree, quantize_tree

VOCAB = 61


def _twins(depth: int, max_len: int = 16):
    """(unscanned decode twin, scanned decode twin, flat params)."""
    model = TransformerLM(vocab=VOCAB, dim=32, depth=depth, num_heads=4)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    dec = decode_model(model, max_len)
    dec_s = dataclasses.replace(dec, scan_layers=True)
    return dec, dec_s, params


def _step_jaxpr(m, params, batch: int = 2, max_len: int = 16):
    cache = init_cache(m, batch, max_len)
    tok = jnp.ones((batch, 1), jnp.int32)
    return jax.make_jaxpr(
        lambda p, c, t: decode_apply(m, p, c, t))(params, cache, tok)


def _eqn_count(jaxpr) -> int:
    return len(jaxpr.jaxpr.eqns)


def test_scanned_step_op_count_depth_invariant_and_lower():
    counts = {}
    for depth in (2, 4):
        dec, dec_s, params = _twins(depth)
        stacked = stack_block_params(params, depth)
        counts[depth] = {
            "unscanned": _eqn_count(_step_jaxpr(dec, params)),
            "scanned": _eqn_count(_step_jaxpr(dec_s, stacked)),
        }
    # the layer loop is gone: adding layers adds ROWS to the stacked
    # operands, not equations to the program
    assert counts[2]["scanned"] == counts[4]["scanned"]
    assert counts[4]["unscanned"] > counts[2]["unscanned"]
    assert counts[4]["scanned"] < counts[4]["unscanned"]


def test_scanned_step_is_one_scan():
    dec, dec_s, params = _twins(4)
    jx = _step_jaxpr(dec_s, stack_block_params(params, 4))
    prims = [e.primitive.name for e in jx.jaxpr.eqns]
    assert prims.count("scan") == 1
    # the unscanned twin's per-layer loop unrolls at trace time: no scan
    jx_flat = _step_jaxpr(dec, params)
    assert all(e.primitive.name != "scan" for e in jx_flat.jaxpr.eqns)


def test_scanned_step_logits_close_to_unscanned():
    """Same math, same order — only XLA's scan-body fusion may move
    float rounding, so the two layouts agree to ~1 ULP, and every
    behavioral surface (the token streams) is pinned EXACT against
    `generate` in test_serve_lm.py."""
    dec, dec_s, params = _twins(3)
    cache_f = init_cache(dec, 2, 16)
    cache_s = init_cache(dec_s, 2, 16)
    tok = jnp.asarray([[5], [11]], jnp.int32)
    lf, _ = decode_apply(dec, params, cache_f, tok)
    ls, _ = decode_apply(dec_s, stack_block_params(params, 3), cache_s, tok)
    np.testing.assert_allclose(np.asarray(lf), np.asarray(ls),
                               rtol=1e-5, atol=1e-5)


def test_scan_compatible_gates_moe():
    from idunno_tpu.models.moe import MoETransformerLM
    assert scan_compatible(TransformerLM(vocab=VOCAB, dim=32, depth=2,
                                         num_heads=4))
    assert not scan_compatible(MoETransformerLM(vocab=VOCAB, dim=32,
                                                depth=2, num_heads=4,
                                                n_experts=2))


def test_scan_layers_model_rejects_flax_apply():
    _, dec_s, params = _twins(2)
    with pytest.raises(ValueError, match="decode_apply"):
        dec_s.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))


def test_stack_block_params_quantized_roundtrip():
    """QTensor is a pytree: q and scale stack independently, and the
    dequantized slice of the stacked tree must equal the dequantized
    original block — quantize-then-stack loses nothing."""
    depth = 3
    model = TransformerLM(vocab=VOCAB, dim=32, depth=depth, num_heads=4)
    params = model.init(jax.random.PRNGKey(1),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    qp = quantize_tree(params)
    dq_stack = dequantize_tree(stack_block_params(qp, depth)["blocks"])
    for i in range(depth):
        ref = dequantize_tree(qp[f"block{i}"])
        got = jax.tree.map(lambda leaf: leaf[i], dq_stack)
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)), ref, got)


def test_bless_slots_picks_knee_not_max():
    from idunno_tpu.utils.lm_bench import bless_slots
    curve = [{"slots": 2, "tokens_per_s": 100.0},
             {"slots": 4, "tokens_per_s": 150.0},
             {"slots": 8, "tokens_per_s": 160.0}]
    b = bless_slots(curve)
    assert b["slots"] == 2                      # 100 >= 0.5 * 160
    assert b["frac_of_max"] == pytest.approx(100 / 160, abs=1e-3)
    assert bless_slots(curve, frac=0.9)["slots"] == 4   # 150 >= 144
    assert bless_slots(curve, frac=0.99)["slots"] == 8  # only the max


def test_tp_step_still_one_scan_and_collectives_depth_invariant():
    """Tensor parallelism must not undo the scan win: the TP specs ride
    the *stacked* leaves, so GSPMD's two per-block psums land INSIDE the
    scan body — the traced step is still ONE `lax.scan`, and the
    compiled program's all-reduce count is depth-invariant (adding
    layers adds rows to the stacked operands, not collectives to the
    program)."""
    from jax.sharding import NamedSharding
    from idunno_tpu.parallel.mesh import make_mesh
    from idunno_tpu.parallel.sharding import lm_cache_specs, shard_lm_params

    mesh = make_mesh(1, 2, devices=jax.devices()[:2])
    counts = {}
    for depth in (2, 4):
        model = TransformerLM(vocab=VOCAB, dim=32, depth=depth,
                              num_heads=4)
        params = model.init(jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
        dec_s = dataclasses.replace(decode_model(model, 16),
                                    scan_layers=True)
        sp = shard_lm_params(mesh, dec_s, params)
        cache = init_cache(dec_s, 2, 16)
        cache = jax.tree.map(
            lambda l, s: jax.device_put(l, NamedSharding(mesh, s)),
            cache, lm_cache_specs(cache, n_model=2))
        tok = jnp.ones((2, 1), jnp.int32)
        jx = jax.make_jaxpr(
            lambda p, c, t: decode_apply(dec_s, p, c, t))(sp, cache, tok)
        prims = [e.primitive.name for e in jx.jaxpr.eqns]
        assert prims.count("scan") == 1, depth
        text = jax.jit(
            lambda p, c, t: decode_apply(dec_s, p, c, t)).lower(
            sp, cache, tok).compile().as_text()
        counts[depth] = text.count("all-reduce")
    assert counts[2] > 0, "TP step must contain model-axis reductions"
    assert counts[2] == counts[4], \
        f"collective count grew with depth: {counts}"


def test_tp_sharded_tail_one_scan_no_sort_depth_invariant():
    """ISSUE 16: the decode step PLUS the fused sampling tail, with the
    unembed column-sharded (vocab 64 divides the 2-wide model axis).
    Still ONE `lax.scan`; the whole traced program carries ZERO
    sort/cumsum primitives (the tail's filters resolve via bit-bisected
    threshold reductions, not a vocab sort); and the compiled all-reduce
    count stays depth-invariant — the picks merge per-shard scalar
    stats, never the [S, vocab] logits."""
    from jax.sharding import NamedSharding
    from idunno_tpu.ops.sampling import fused_decode_tail
    from idunno_tpu.parallel.mesh import make_mesh
    from idunno_tpu.parallel.sharding import lm_cache_specs, shard_lm_params

    mesh = make_mesh(1, 2, devices=jax.devices()[:2])
    S, max_len, vocab = 2, 16, 64
    ar_counts = {}
    for depth in (2, 4):
        model = TransformerLM(vocab=vocab, dim=32, depth=depth,
                              num_heads=4)
        params = model.init(jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
        dec_s = dataclasses.replace(decode_model(model, max_len),
                                    scan_layers=True)
        sp = shard_lm_params(mesh, dec_s, params)
        cache = init_cache(dec_s, S, max_len)
        cache = jax.tree.map(
            lambda l, s: jax.device_put(l, NamedSharding(mesh, s)),
            cache, lm_cache_specs(cache, n_model=2))

        def step(p, c, tokens, cursors, remaining, keys, logprobs, cnts):
            # mirrors engine/serve_lm._build_decode's body: model step,
            # then the one fused tail with every feature flag ON
            tok = jnp.take_along_axis(tokens, cursors[:, None], axis=1)
            logits, c = decode_apply(dec_s, p, c, tok)
            out = fused_decode_tail(
                logits[:, 0], tokens, cursors, remaining,
                jnp.full((S,), 0.9, jnp.float32),
                jnp.full((S,), 0.8, jnp.float32),
                jnp.full((S,), 5, jnp.int32),
                keys, logprobs,
                jnp.full((S,), 0.5, jnp.float32),
                jnp.full((S,), 0.25, jnp.float32), cnts,
                max_len=max_len, eos_id=None, track=True, pen=True)
            return out, c

        args = (sp, cache,
                jnp.zeros((S, max_len), jnp.int32),
                jnp.full((S,), 3, jnp.int32),       # cursors
                jnp.full((S,), 5, jnp.int32),       # remaining
                jnp.zeros((S, 2), jnp.uint32),      # raw rng keys
                jnp.zeros((S, max_len), jnp.float32),
                jnp.zeros((S, vocab), jnp.int32))
        jx = jax.make_jaxpr(step)(*args)
        prims = [e.primitive.name for e in jx.jaxpr.eqns]
        assert prims.count("scan") == 1, depth
        # recursive primitive walk: the sampled branch lives inside a
        # lax.cond, so a vocab sort there would not show in the
        # top-level eqn list
        names, stack = set(), [jx.jaxpr]
        while stack:
            j = stack.pop()
            for e in j.eqns:
                names.add(e.primitive.name)
                for v in e.params.values():
                    for x in (v if isinstance(v, (list, tuple)) else [v]):
                        if getattr(x, "jaxpr", None) is not None:
                            stack.append(x.jaxpr)
        for banned in ("sort", "cumsum", "cummax", "top_k",
                       "approx_top_k"):
            assert banned not in names, \
                f"{banned} primitive in the fused-tail step at depth {depth}"
        compiled = jax.jit(step).lower(*args).compile().as_text()
        ar_counts[depth] = compiled.count("all-reduce")
    assert ar_counts[2] > 0, "TP step must contain model-axis reductions"
    assert ar_counts[2] == ar_counts[4], \
        f"collective count grew with depth: {ar_counts}"


# -- the layer scan carries the cache: what a step writes, and only that -----

_DEPTH, _MAX, _BS = 3, 24, 4
# (rows, tokens a row, cursors): the per-row step, the per-row chunk,
# the scalar-cursor chunk (chunked prefill)
_SHAPES = {"per-row-step": (3, 1, (9, 13, 8)),
           "per-row-chunk": (3, 4, (9, 13, 8)),
           "scalar-chunk": (1, 5, 9)}


def _carried_case(shape: str, quant: bool, paged: bool, cursors=None,
                  max_len: int = _MAX, kv_heads: int = 2):
    """(decode twin, stacked params, a cache full of noise with its cursors
    set, tokens, paged context or None)."""
    from idunno_tpu.ops.paged_attention import PagedContext

    rows, t, cur = _SHAPES[shape]
    cur = cur if cursors is None else cursors
    model = TransformerLM(vocab=VOCAB, dim=32, depth=_DEPTH, num_heads=4,
                          num_kv_heads=kv_heads,
                          kv_cache_dtype="int8" if quant else "native")
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    dec = dataclasses.replace(decode_model(model, max_len), scan_layers=True,
                              decode_per_row=rows > 1)
    keys = iter(jax.random.split(jax.random.PRNGKey(7), 16))

    def noise(leaf):
        if leaf.dtype == jnp.int32:                       # the cursors
            return jnp.broadcast_to(jnp.asarray(cur, jnp.int32), leaf.shape)
        if leaf.dtype == jnp.int8:
            return jax.random.randint(next(keys), leaf.shape, -127, 128,
                                      jnp.int32).astype(jnp.int8)
        x = jax.random.normal(next(keys), leaf.shape, jnp.float32)
        return jnp.abs(x) / 64 if leaf.ndim == 4 else x   # scales: 4-D
    cache = jax.tree.map(noise, init_cache(dec, rows, max_len))
    ctx = None
    if paged:
        store = cache["attn"]
        pages = {k: jax.tree.map(noise, jnp.zeros(
            (_DEPTH, 6, _BS) + v.shape[3:], v.dtype))
            for k, v in store.items() if v.ndim >= 4}
        ctx = PagedContext(
            pages["cached_k"], pages["cached_v"],
            jnp.asarray([[1, 4], [2, 0], [0, 0]], jnp.int32)[:rows],
            jnp.asarray([8, 4, 0], jnp.int32)[:rows],
            k_scale_pages=pages.get("k_scale"),
            v_scale_pages=pages.get("v_scale"), start=0, kernel="xla")
    tok = jax.random.randint(jax.random.PRNGKey(3), (rows, t), 0, VOCAB)
    return dec, stack_block_params(params, _DEPTH), cache, tok, ctx


def _per_layer_loop(dec, params, cache, tok, ctx):
    """The same step, one `Block.apply` a layer over that layer's own
    slice of parameters, cache and pages: what the scan did before it
    carried the cache, unrolled."""
    import flax.linen as nn
    from idunno_tpu.models.transformer import Block

    blk = Block(dec.dim, dec.num_heads, num_kv_heads=dec.num_kv_heads,
                decode=True, max_decode_len=dec.max_decode_len,
                decode_per_row=dec.decode_per_row,
                kv_cache_dtype=dec.kv_cache_dtype)
    h = nn.Embed(dec.vocab, dec.dim).apply({"params": params["embed"]}, tok)
    layers = []
    for i in range(dec.depth):
        def at(tree):
            return jax.tree.map(lambda x: x[i], tree)
        pg = None if ctx is None else ctx.layer(
            ctx.k_pages[i], ctx.v_pages[i], at(ctx.k_scale_pages),
            at(ctx.v_scale_pages))
        h, mut = blk.apply({"params": at(params["blocks"]),
                            "cache": at(cache)}, h, paged=pg,
                           mutable=["cache"])
        layers.append(mut["cache"])
    h = nn.LayerNorm().apply({"params": params["ln_f"]}, h)
    logits = nn.Dense(dec.vocab).apply({"params": params["head"]}, h)
    return logits, jax.tree.map(lambda *xs: jnp.stack(xs), *layers)


def _written(shape: str, leaf_shape, cursors=None) -> np.ndarray:
    """Mask over a K/V or scale leaf [L, rows, T, ...] of the positions a
    step writes: every layer, each row's cursor .. cursor + t - 1."""
    rows, t, cur = _SHAPES[shape]
    cur = np.broadcast_to(np.asarray(cur if cursors is None else cursors),
                          (rows,))
    mask = np.zeros(leaf_shape, bool)
    for r in range(rows):
        if cur[r] + t <= _MAX:                  # an overflowing row: none
            mask[:, r, cur[r]:cur[r] + t] = True
    return mask


@pytest.mark.parametrize("paged", [False, True], ids=["slot", "paged-xla"])
@pytest.mark.parametrize("quant", [False, True], ids=["native", "int8"])
@pytest.mark.parametrize("shape", list(_SHAPES))
def test_scanned_step_writes_only_the_new_rows(shape, quant, paged):
    """The scan that carries the stacked cache gives the per-layer loop's
    logits, leaves every byte of the cache as it was but the new tokens'
    rows at (layer, row, position), and puts there what the loop puts."""
    dec, params, cache, tok, ctx = _carried_case(shape, quant, paged)
    want_logits, want = _per_layer_loop(dec, params, cache, tok, ctx)
    logits, new = jax.jit(
        lambda p, c, t, g: decode_apply(dec, p, c, t, paged=g))(
        params, cache, tok, ctx)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want_logits),
                               rtol=1e-5, atol=1e-5)
    assert jax.tree.structure(new) == jax.tree.structure(cache)
    for name, before in cache["attn"].items():
        after, ref = np.asarray(new["attn"][name]), np.asarray(
            want["attn"][name])
        before = np.asarray(before)
        if before.dtype == np.int32:            # cursor(s)
            np.testing.assert_array_equal(after, ref)
            continue
        mask = _written(shape, before.shape)
        assert mask.any() and after.dtype == before.dtype
        np.testing.assert_array_equal(after[~mask], before[~mask])
        assert (after[mask] != before[mask]).any()
        # an int8 value may round the other way where the scan body's
        # fusion moved the float by an ulp
        np.testing.assert_allclose(
            after[mask].astype(np.float32), ref[mask].astype(np.float32),
            rtol=1e-5, atol=1.0 if before.dtype == np.int8 else 1e-5)


@pytest.mark.parametrize("quant", [False, True], ids=["native", "int8"])
@pytest.mark.parametrize("shape,cursors", [
    ("per-row-step", (_MAX, 13, 8)), ("per-row-chunk", (_MAX - 3, 13, 8)),
    ("scalar-chunk", _MAX - 4)])
def test_overflow_leaves_the_carried_cache_untouched(shape, cursors, quant):
    """A row whose chunk would run past ``max_len``: none of its cache rows
    change, in any layer, and its scores are poisoned; the other rows
    write and answer as ever."""
    dec, params, cache, tok, _ = _carried_case(shape, quant, False,
                                               cursors=cursors)
    logits, new = decode_apply(dec, params, cache, tok)
    over = (np.broadcast_to(np.asarray(cursors), (tok.shape[0],))
            + tok.shape[1]) > _MAX
    assert over.any()
    assert np.isnan(np.asarray(logits)[over]).all()
    assert np.isfinite(np.asarray(logits)[~over]).all()
    for name, before in cache["attn"].items():
        if before.dtype == jnp.int32:
            continue
        after, before = np.asarray(new["attn"][name]), np.asarray(before)
        mask = _written(shape, before.shape, cursors)
        np.testing.assert_array_equal(after[~mask], before[~mask])
        assert not mask[:, over].any()
        if (~over).any():
            assert (after[mask] != before[mask]).any()


# -- the context ladder: the per-row step reads the deepest row's rung --------

@pytest.mark.parametrize("max_len,want", [
    (4096, (512, 1024, 1536, 2048, 2560, 3072, 3584, 4096)),
    (512, (128, 256, 384, 512)),
    (300, (128, 256, 300)), (128, (128,)), (24, (24,)), (1, (1,))])
def test_context_rungs_ascend_to_max_len(max_len, want):
    """Whole tiles of an eighth of the cache (128 at least), the last rung
    the cache itself; a cache of one tile or less has the one rung."""
    rungs = context_rungs(max_len)
    assert rungs == want
    assert list(rungs) == sorted(set(rungs)) and rungs[-1] == max_len


_LADDER = {"native-gqa": (False, False, 2), "native-mha": (False, False, 4),
           "int8-gqa": (True, False, 2), "int8-mha": (True, False, 4),
           "paged-native": (False, True, 2), "paged-int8": (True, True, 2)}


def _whole_axis(fn, *args):
    """``fn`` traced with a one-rung ladder: the per-row step reads the
    whole token axis in one piece, which is what it did before it had a
    ladder."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(transformer, "context_rungs", lambda n: (n,))
        return jax.jit(fn)(*args)


@pytest.mark.parametrize("shape", ["per-row-step", "per-row-chunk"])
@pytest.mark.parametrize("kind", list(_LADDER))
@pytest.mark.parametrize("max_len,deepest", [
    (512, 9), (512, 127), (512, 128), (512, 300), (512, 400), (512, 500),
    (300, 200), (300, 290)])
def test_every_rung_answers_as_the_whole_axis(max_len, deepest, kind, shape):
    """Rows placed so that each rung is taken in turn (the deepest row
    just under a rung, on it, in the last rung that is no whole tile):
    logits and cache equal those of the same step reading the whole axis
    at once. The rows behind the deepest are shallower, one of them with a
    paged chain longer than its first tile holds live."""
    quant, paged, kv = _LADDER[kind]
    t = _SHAPES[shape][1]
    deepest = min(deepest, max_len - t)
    cursors = (9, deepest, 140 if deepest > 140 else 8)
    dec, params, cache, tok, ctx = _carried_case(
        shape, quant, paged, cursors=cursors, max_len=max_len, kv_heads=kv)
    rungs = context_rungs(max_len)
    assert len(rungs) > 1

    def step(p, c, t_, g):
        return decode_apply(dec, p, c, t_, paged=g)
    want_logits, want = _whole_axis(step, params, cache, tok, ctx)
    logits, new = jax.jit(step)(params, cache, tok, ctx)
    assert np.isfinite(np.asarray(logits)).all()
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want_logits),
                               rtol=2e-5, atol=2e-5)
    for name, ref in want["attn"].items():
        np.testing.assert_allclose(
            np.asarray(new["attn"][name]).astype(np.float32),
            np.asarray(ref).astype(np.float32), rtol=1e-5,
            atol=1.0 if ref.dtype == jnp.int8 else 1e-5)


def test_the_ladder_reads_no_further_than_the_deepest_row():
    """Noise past the deepest row's rung does not reach the logits, NaN
    there included: the tiles beyond the rung are not read at all (a
    masked read of a NaN key would still poison the product)."""
    dec, params, cache, tok, _ = _carried_case(
        "per-row-step", False, False, cursors=(9, 200, 140), max_len=512)
    logits, _ = decode_apply(dec, params, cache, tok)

    def beyond(leaf):
        if leaf.dtype == jnp.int32:
            return leaf
        return leaf.at[:, :, 256:].set(jnp.nan)
    poisoned, _ = decode_apply(dec, params, jax.tree.map(beyond, cache), tok)
    np.testing.assert_array_equal(np.asarray(poisoned), np.asarray(logits))


def test_the_per_layer_loop_takes_the_ladder_too():
    """The flax per-layer loop (models with an `ffn_factory`) runs the same
    branch with no stacked leaf: its logits on a middle rung are the
    scan's."""
    dec, params, cache, tok, _ = _carried_case(
        "per-row-step", False, False, cursors=(9, 300, 140), max_len=512)
    want_logits, _ = _per_layer_loop(dec, params, cache, tok, None)
    logits, _ = decode_apply(dec, params, cache, tok)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want_logits),
                               rtol=1e-5, atol=1e-5)

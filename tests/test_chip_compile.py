"""The main path's Pallas kernels, compiled for a described TPU v5e.

The chip's compiler (Mosaic, inside libtpu) is installed here and compiles
for a chip that is described and not attached, so a kernel it refuses
fails in tier-1, without chip time. Interpret mode — what every other
kernel test runs — accepts block shapes Mosaic does not: the paged decode
kernel of PR 7 / PR 15 passed every interpret test and was refused for
every KVH > 1 (block of 1 on the second-to-last axis).

Rules this file keeps (`/opt/skills/guides/on-chip-measurement`, §2): the
topology is described inside a module-scoped fixture that skips when it
cannot be; shardings and shapes are built in fixtures or tests; nothing
touches `topologies`, libtpu or a TPU device while any module is imported —
the driver's six xdist workers each import this file, and only the worker
that RUNS it may load the library. All such compiles live in this ONE file.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from idunno_tpu.engine.generate import init_cache
from idunno_tpu.engine.kv_blocks import _WRITE_GROUP, _write_block
from idunno_tpu.engine.serve_lm import _DECODE_DONATED, DecodeServer
from idunno_tpu.models.transformer import (TransformerLM, context_rungs,
                                           decode_apply, stack_block_params)
from idunno_tpu.ops.flash_attention import flash_attention
from idunno_tpu.ops.paged_attention import paged_attention_grouped
from idunno_tpu.ops.pallas_preprocess import preprocess_batch_pallas


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu here: nothing to test
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip (the next one would warn and
    # compile again): keep these out of it, and put the setting back
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, one_chip, *shapes, **kw_shapes):
    """Lower ``fn`` at (shape, dtype) specs placed on the described chip."""
    def spec(sd):
        return jax.ShapeDtypeStruct(sd[0], sd[1], sharding=one_chip)
    args = [spec(s) for s in shapes]
    kwargs = {k: spec(s) for k, s in kw_shapes.items()}
    return jax.jit(fn).lower(*args, **kwargs).compile().as_text()


def test_flash_forward_lm_bench_shape(one_chip):
    qkv = ((4, 1024, 16, 64), jnp.bfloat16)
    text = _compiled_text(
        lambda q, k, v: flash_attention(q, k, v, causal=True),
        one_chip, qkv, qkv, qkv)
    assert "tpu_custom_call" in text


def test_flash_backward_lm_bench_shape(one_chip):
    qkv = ((4, 1024, 16, 64), jnp.bfloat16)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True).astype(
            jnp.float32).sum()
    text = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)),
                          one_chip, qkv, qkv, qkv)
    # forward (recomputed lse) + the dq and dk/dv kernels
    assert text.count("tpu_custom_call") >= 3


def test_flash_forward_vit_shape(one_chip):
    qkv = ((8, 197, 6, 64), jnp.bfloat16)        # ViT-S/16: 196 patches+cls
    text = _compiled_text(
        lambda q, k, v: flash_attention(q, k, v, causal=False),
        one_chip, qkv, qkv, qkv)
    assert "tpu_custom_call" in text


def test_pallas_preprocess_batch_256(one_chip):
    text = _compiled_text(
        lambda u8: preprocess_batch_pallas(u8, crop=224),
        one_chip, ((256, 256, 256, 3), jnp.uint8))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("int8", [False, True], ids=["native", "int8"])
@pytest.mark.parametrize("kvh,d,bs", [(16, 64, 16), (4, 64, 16),
                                      (1, 64, 16), (4, 128, 32)])
def test_paged_decode_kernel(one_chip, kvh, d, bs, int8):
    """One decode step's worth: 16 slots, one query token, 16 query heads
    grouped over ``kvh`` kv-heads, a 512-block pool, chains of 8."""
    slots, heads, n, c = 16, 16, 512, 8
    pages = ((n, bs, kvh, d), jnp.int8 if int8 else jnp.bfloat16)
    kw = {}
    if int8:
        scales = ((n, bs, kvh), jnp.float32)
        kw = {"k_scale_pages": scales, "v_scale_pages": scales}
    text = _compiled_text(
        lambda q5, kp, vp, tables, lengths, **k: paged_attention_grouped(
            q5, kp, vp, tables, lengths, kernel="pallas", interpret=False,
            **k),
        one_chip, ((slots, 1, kvh, heads // kvh, d), jnp.float32), pages,
        pages, ((slots, c), jnp.int32), ((slots,), jnp.int32), **kw)
    assert "tpu_custom_call" in text


def test_paged_prefill_suffix_rows_fit_vmem(one_chip):
    """A 512-token suffix attending its radix hit through the table: the
    query rows are tiled so the kernel's blocks and scratch stay inside
    VMEM (all kv-heads ride in one program)."""
    kvh, d, bs, t, c = 16, 64, 16, 512, 32
    pages = ((512, bs, kvh, d), jnp.bfloat16)
    text = _compiled_text(
        lambda q5, kp, vp, tables, lengths: paged_attention_grouped(
            q5, kp, vp, tables, lengths, kernel="pallas", interpret=False),
        one_chip, ((1, t, kvh, 1, d), jnp.float32), pages, pages,
        ((1, c), jnp.int32), ((1,), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("store,row,dtype,stacked,n", [
    ((30, 3584, 16, 2, 128), (30, 1, 1024, 2, 128), jnp.bfloat16, True,
     _WRITE_GROUP),
    ((16, 1024, 16, 4, 128), (16, 1, 64, 4, 128), jnp.bfloat16, True,
     _WRITE_GROUP),
    ((16, 1024, 16, 4, 128), (16, 1, 1024, 4, 128), jnp.int8, True,
     _WRITE_GROUP),
    ((16, 1024, 16, 4), (16, 1, 1024, 4), jnp.float32, True, _WRITE_GROUP),
    ((12, 2048, 16, 16, 64), (12, 1, 512, 16, 64), jnp.bfloat16, True,
     _WRITE_GROUP),
    ((1024, 16, 4, 128), (1, 1024, 4, 128), jnp.bfloat16, False,
     _WRITE_GROUP),
    ((16, 1024, 16, 4, 128), (16, 1, 16, 4, 128), jnp.bfloat16, True, 1),
], ids=["starcoder2-3b", "starcoder2-7b-row64", "int8", "int8-scales",
        "mha-d64", "unstacked", "raw-sliver"])
def test_block_write_updates_the_store_in_place(one_chip, store, row, dtype,
                                                stacked, n):
    """`_write_block` at the benchmark's pools (and the shapes whose block
    axis the chip lays out minor: scale leaves, head size 64), ``n`` blocks
    a dispatch: the output aliases the donated store and the program's own
    memory stays far under one store — no copy of the pool in, out or
    beside it. A loop or a scatter over the blocks fails this for the
    last-named shapes."""
    compiled = _write_block.lower(
        jax.ShapeDtypeStruct(store, dtype, sharding=one_chip),
        jax.ShapeDtypeStruct(row, dtype, sharding=one_chip),
        jax.ShapeDtypeStruct((2, n), jnp.int32, sharding=one_chip),
        stacked=stacked).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == mem.output_size_in_bytes
    assert mem.temp_size_in_bytes < mem.output_size_in_bytes // 4


@pytest.mark.parametrize("which", ["decode", "chunk"])
def test_hybrid_stack_steps_at_published_widths(one_chip, which):
    """A sparse and a linear layer of `models/hybrid.py` at MiniCPM-SALA's
    published widths: the decode step over the benchmark's 16 slots x 16896
    (top-k, the gather of selected blocks, the state update) and a
    2048-token prefill chunk of a 16384-token row (the selection mask over
    key tiles with a running softmax) pass the chip's compiler and fit its
    memory. Plain XLA: no custom call is looked for."""
    import dataclasses

    from idunno_tpu.models.hybrid import LINEAR, SPARSE, HybridLM

    dt = jnp.bfloat16
    model = HybridLM(
        vocab=73448, dim=4096, mlp_dim=16384, mixers=(SPARSE, LINEAR),
        layer_ids=(9, 10), published_depth=32, num_heads=32, num_kv_heads=2,
        head_dim=128, lightning_heads=32, lightning_head_dim=128,
        scale_emb=12.0, scale_depth=1.4, logit_div=16.0, dtype=dt,
        param_dtype=dt)

    def sds(shape, dtype=dt):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def layer(heads):
        return {"ln1": sds((1, 4096)), "wq": sds((1, 4096, 32, 128)),
                "wk": sds((1, 4096, heads, 128)),
                "wv": sds((1, 4096, heads, 128)), "qn": sds((1, 128)),
                "kn": sds((1, 128)), "wz": sds((1, 4096, 32, 128)),
                "wo": sds((1, 32, 128, 4096)), "ln2": sds((1, 4096)),
                "wg": sds((1, 4096, 16384)), "wu": sds((1, 4096, 16384)),
                "wd": sds((1, 16384, 4096))}
    params = {"embed": sds((73448, 4096)),
              "runs": (layer(2), dict(layer(32), on=sds((1, 32, 128)))),
              "norm_f": sds((4096,)), "head": sds((4096, 73448))}
    if which == "decode":
        dec = dataclasses.replace(model, decode=True, decode_per_row=True,
                                  max_decode_len=16896)
        rows, tokens = 16, 1
    else:
        dec = dataclasses.replace(model, decode=True, max_decode_len=16384)
        rows, tokens = 1, 2048
    cache = jax.tree.map(lambda s: sds(s.shape, s.dtype),
                         jax.eval_shape(lambda: dec.init_cache(rows)))
    compiled = jax.jit(dec.decode_apply).lower(
        params, cache, sds((rows, tokens), jnp.int32)).compile()
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes) < 12e9


def _granite_layer(sds, kind: str) -> dict:
    """Shapes of one stacked layer of granite-4.0-h-small at its published
    widths, 36 of its 72 experts held."""
    ffn = {"ln2": sds((1, 4096)), "router": sds((1, 4096, 72)),
           "w1": sds((1, 36, 4096, 1536)), "w2": sds((1, 36, 768, 4096)),
           "ws1": sds((1, 4096, 3072)), "ws2": sds((1, 1536, 4096))}
    if kind == "attention":
        return {"ln1": sds((1, 4096)), "wq": sds((1, 4096, 32, 128)),
                "wk": sds((1, 4096, 8, 128)), "wv": sds((1, 4096, 8, 128)),
                "wo": sds((1, 32, 128, 4096)), **ffn}
    return {"ln1": sds((1, 4096)), "w_in": sds((1, 4096, 16768)),
            "conv_w": sds((1, 4, 8448)), "conv_b": sds((1, 8448)),
            "dt_bias": sds((1, 128), jnp.float32),
            "A_log": sds((1, 128), jnp.float32), "D": sds((1, 128)),
            "norm": sds((1, 8192)), "w_out": sds((1, 8192, 4096)), **ffn}


@pytest.mark.parametrize("which", ["decode", "chunk"])
@pytest.mark.parametrize("kind", ["mamba2", "attention"])
def test_granite_layers_at_published_widths(one_chip, kind, which):
    """One layer of each new kind of `models/hybrid.py` with its routed and
    shared experts at granite-4.0-h-small's published widths (a selective
    scan over 128 x 64 x 128 states, GQA 32/8 x 128 without positions,
    top-10 of 72 with 36 held): the decode step over the benchmark's 48
    slots x 4864 (every token by every held expert) and a 2048-token
    prefill chunk of a 4096-token row (the chunked scan, attention a tile
    of queries at a time, tokens grouped by expert: the chip's own
    grouped product, a custom call) pass the chip's compiler, and their
    temporaries stay bounded."""
    from idunno_tpu.models.hybrid import MOE, HybridLM

    dt = jnp.bfloat16
    model = HybridLM(
        vocab=100352, dim=4096, mlp_dim=768, mixers=(kind,), layer_ids=(0,),
        published_depth=1, scale_depth=0.22, num_heads=32, num_kv_heads=8,
        head_dim=128, attn_scale=0.0078125, scale_emb=12.0, logit_div=16.0,
        eps=1e-5, ssm_heads=128, ssm_head_dim=64, ssm_state=128, ffn=MOE,
        experts=72, experts_per_token=10, experts_held=(0, 36),
        shared_dim=1536, dtype=dt, param_dtype=dt)

    def sds(shape, dtype=dt):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = {"embed": sds((100352, 4096)),
              "runs": (_granite_layer(sds, kind),), "norm_f": sds((4096,))}
    if which == "decode":
        dec = dataclasses.replace(model, decode=True, decode_per_row=True,
                                  max_decode_len=4864)
        rows, tokens = 48, 1
    else:
        dec = dataclasses.replace(model, decode=True, max_decode_len=4096)
        rows, tokens = 1, 2048
    cache = jax.tree.map(lambda s: sds(s.shape, s.dtype),
                         jax.eval_shape(lambda: dec.init_cache(rows)))
    compiled = jax.jit(dec.decode_apply).lower(
        params, cache, sds((rows, tokens), jnp.int32)).compile()
    mem = compiled.memory_analysis()
    # the grouped product is there where tokens are many, and only there
    assert ("ragged-dot" in compiled.as_text()) == (which == "chunk")
    assert mem.temp_size_in_bytes < 3e9
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes) < 8e9


def test_granite_dispatch_updates_states_and_kv_in_place(one_chip):
    """`DecodeServer._build_decode`'s `jit_run` over a stack of two mamba2
    layers and an attention layer at granite-4.0-h-small's widths, 48 slots
    x 4864 (a toy pool whose `_dec` is widened after the build): the new
    cache IS the donated one, the program's own memory holds no second
    copy of the scan states (their run's scan carries them and a layer
    updates its own where it lies), and no whole stacked state is copied."""
    from benchmark import manifest, system

    man = manifest.Manifest()
    cfg = man.config(man.cell("granite-4.0-h-small.chat"))
    fam = man.family(cfg)
    cut = dict(layer_types=["mamba", "mamba", "attention"],
               num_hidden_layers=3)
    cfg = dict(cfg, **cut)
    toy = dict(system.model_config(cfg, True, fam), **cut,
               vocab_size=cfg["vocab_size"])
    model, params, _kw = fam.program.build(
        toy, fam.weights.make_weights(toy, 1))
    slots, max_len = 48, 4864
    srv = DecodeServer(model, params, slots=slots, prompt_len=128,
                       max_len=max_len, decode_steps=4,
                       prompt_buckets=(128,), kv_block_size=64,
                       kv_cache_blocks=4)
    srv._dec = dataclasses.replace(
        fam.program.model_of(cfg), decode=True, decode_per_row=True,
        max_decode_len=max_len)
    wide = _described(fam.program.program_params(jax.eval_shape(
        lambda: fam.weights.make_weights(cfg, 1))), one_chip)
    cache = _described(jax.eval_shape(
        lambda: srv._dec.init_cache(slots)), one_chip)
    compiled = _compile_run(srv, wide, cache, one_chip)
    mem = compiled.memory_analysis()
    states = 2 * slots * 128 * 64 * 128 * 4
    assert mem.alias_size_in_bytes >= _nbytes(cache)
    assert mem.temp_size_in_bytes < states // 4
    text = compiled.as_text()
    assert not re.findall(r"= f32\[2,48,128,64,128\]\S* copy\(", text)
    # the mamba2 run's decode step is the live-rows kernel, in place: no
    # layer's slice of every slot's state [48, 128, 64, 128] is formed
    assert _live_state_steps(text, "2,48,128,64,128") == 1
    assert not re.findall(r"= f32\[(?:1,)?48,128,64,128\]", text)
    # the attention layer reads its K/V a tile of the context ladder at a
    # time (ISSUE 37): no [48, 4864, 8, 128] slice or copy of it. Sixteen
    # tiles, not eight: a tile of 608 tokens is 60 MB of K and 60 of V,
    # and only one of the two was staged in the chip's fast memory
    # (`S(1)`); at 304 both are
    assert srv._dec.decode_context_rungs(max_len, slots)[0] == 304
    assert _kv_reads(text, slots, 8) == [304]
    staged = re.findall(r"= bf16\[1,48,304,8,128\]\{[^}]*\} fusion\(", text)
    assert len(staged) == 2 and all("S(1)" in s for s in staged)


def _live_state_steps(text: str, states: str) -> int:
    """How many `ops/ssd_decode.py` kernels of a compiled program hand
    back the whole stacked state ``f32[<states>]`` (aliased in to out)."""
    return len(re.findall(
        rf"%ssd_decode\S* = \(f32\[{states}\]\S* f32\[[\d,]+\]\S*\) "
        r"custom-call\(", text))


def _kv_reads(text: str, rows: int, kv_heads: int,
              head_dim: int = 128) -> list[int]:
    """The token-axis lengths of every slice or copy of ``rows`` slots' K
    or V (`[rows, n, kv_heads, head_dim]`, a leading 1 aside) in a compiled
    program: what a layer stages of its cache to attend over it."""
    return sorted({int(n) for n in re.findall(
        rf"= bf16\[(?:1,)?{rows},(\d+),{kv_heads},{head_dim}\]\S* "
        r"(?:copy|dynamic-slice)\(", text)})


def _falcon_pool(slots: int, max_len: int):
    """A toy pool of the family `falcon_h1` (its rehearsal's widths, the
    published vocabulary's logits aside) and the benchmark's configuration:
    the pool's `_dec` is widened by the caller, nothing of the real size is
    allocated."""
    from benchmark import manifest, system

    man = manifest.Manifest()
    cfg = man.config(man.cell("falcon-h1-34b-instruct.reasoning"))
    fam = man.family(cfg)
    toy = system.model_config(cfg, True, fam)
    model, params, _kw = fam.program.build(
        toy, fam.weights.make_weights(toy, 1))
    srv = DecodeServer(model, params, slots=slots, prompt_len=128,
                       max_len=max_len, decode_steps=4,
                       prompt_buckets=(128,), kv_block_size=64,
                       kv_cache_blocks=4)
    return cfg, fam, srv


def test_falcon_h1_dispatch_updates_kv_and_states_in_place(one_chip):
    """`DecodeServer._build_decode`'s `jit_run` over Falcon-H1's six layers
    of BOTH mixers at the published widths, 32 slots x 4096 (a toy pool
    whose `_dec` is widened after the build): the new cache IS the donated
    one (K/V 1.6 GB and float32 states 0.8 GB, both the carry of the one
    scan), the program's own memory holds no second copy of either, no
    whole stacked leaf is copied, and a layer's K/V is read a tile of the
    context ladder at a time (ISSUE 37)."""
    slots, max_len = 32, 4096
    cfg, fam, srv = _falcon_pool(slots, max_len)
    srv._dec = dataclasses.replace(
        fam.program.model_of(cfg), decode=True, decode_per_row=True,
        max_decode_len=max_len)
    wide = _described(fam.program.program_params(jax.eval_shape(
        lambda: fam.weights.make_weights(cfg, 1))), one_chip)
    cache = _described(jax.eval_shape(
        lambda: srv._dec.init_cache(slots)), one_chip)
    compiled = _compile_run(srv, wide, cache, one_chip)
    mem = compiled.memory_analysis()
    states = 6 * slots * 32 * 128 * 256 * 4
    assert _nbytes(cache) > 2.4e9 and states > 0.8e9
    assert mem.alias_size_in_bytes >= _nbytes(cache)
    # 0.72 GB of temporaries, none a cache: the in-projection's stack
    # [6, 5120, 9248] re-laid out once a dispatch (0.57 GB: 9248 is no
    # multiple of 128 and the chip holds the argument with 5120 minor) and
    # a layer's slice of it (0.09 GB). Until ISSUE 37 0.79 GB: one layer's K
    # or V [32, 4096, 4, 128] was staged whole (0.13 GB), where now a tile
    # [32, 512, 4, 128] of each is (0.017 GB; PERF.md section 7)
    assert mem.temp_size_in_bytes < 0.75e9 < _nbytes(cache) // 2
    text = compiled.as_text()
    assert not re.findall(r"= f32\[6,32,32,128,256\]\S* copy\(", text)
    assert not re.findall(r"= bf16\[6,32,4096,4,128\]\S* copy\(", text)
    # the state-space half is the live-rows kernel, in place: no layer's
    # slice of every slot's state [32, 32, 128, 256] is updated or read out
    assert _live_state_steps(text, "6,32,32,128,256") == 1
    assert not re.findall(r"= f32\[(?:1,)?32,32,128,256\]", text)
    assert srv._dec.decode_context_rungs(max_len, slots)[0] == 512
    assert _kv_reads(text, slots, 4) == [512]


@pytest.mark.parametrize("bucket, tokens", [(2048, 2048), (2048, 256)],
                         ids=["prefill2048", "chunk256-of-2048"])
def test_falcon_h1_prefill_at_published_widths(one_chip, bucket, tokens):
    """A 2048-token prefill of Falcon-H1's six layers at the published
    widths, whole and as a 256-token chunk of a 2048-token row (the cell's
    `prefill_chunk`): the chunked
    scan over [32, 128, 256] states, attention a tile of queries at a time
    and the head over ONE position (every position's float32 logits over
    261120 rows would be 2.1 GB): temporaries under 3 GB beside 10.5 GB of
    weights, the row's cache updated where it lies."""
    from benchmark import manifest

    man = manifest.Manifest()
    cfg = man.config(man.cell("falcon-h1-34b-instruct.reasoning"))
    fam = man.family(cfg)
    dec = dataclasses.replace(fam.program.model_of(cfg), decode=True,
                              max_decode_len=bucket)
    wide = _described(fam.program.program_params(jax.eval_shape(
        lambda: fam.weights.make_weights(cfg, 1))), one_chip)
    cache = _described(jax.eval_shape(lambda: dec.init_cache(1)), one_chip)
    compiled = jax.jit(dec.decode_apply, donate_argnums=(1,)).lower(
        wide, cache, jax.ShapeDtypeStruct((1, tokens), jnp.int32,
                                          sharding=one_chip)).compile()
    mem = compiled.memory_analysis()
    assert 10.4e9 < mem.argument_size_in_bytes < 10.7e9
    assert mem.temp_size_in_bytes < 3e9
    assert mem.alias_size_in_bytes >= _nbytes(cache)
    assert mem.output_size_in_bytes < _nbytes(cache) + 2 * 261120 * 4


def _qwen3_next():
    from benchmark import manifest

    man = manifest.Manifest()
    cfg = man.config(man.cell("qwen3-next-80b-a3b-instruct.long-context"))
    return cfg, man.family(cfg)


def test_qwen3_next_dispatch_updates_states_and_kv_in_place(one_chip):
    """`DecodeServer._build_decode`'s `jit_run` over Qwen3-Next's four layers
    (three Gated DeltaNet, one gated attention) at the published widths, 16
    slots x 33792 (a toy pool whose `_dec` is widened after the build): the
    new cache IS the donated one (K/V 1.1 GB, float32 delta-rule states 0.1
    GB), no whole stacked state is copied and no layer's slice of every
    slot's state is formed (the live rows' are stepped one at a time where
    they lie), the experts are grouped (16 rows x 10 picks of 512), and the
    attention layer reads its K/V a tile of the context ladder at a time,
    each tile staged in the chip's fast memory."""
    from benchmark import system

    cfg, fam = _qwen3_next()
    toy = system.model_config(cfg, True, fam)
    model, params, _kw = fam.program.build(
        toy, fam.weights.make_weights(toy, 1))
    slots, max_len = 16, 33792
    srv = DecodeServer(model, params, slots=slots, prompt_len=512,
                       max_len=max_len, decode_steps=4,
                       prompt_buckets=(512,), kv_block_size=64,
                       kv_cache_blocks=4)
    srv._dec = dataclasses.replace(
        fam.program.model_of(cfg), decode=True, decode_per_row=True,
        max_decode_len=max_len)
    wide = _described(fam.program.program_params(jax.eval_shape(
        lambda: fam.weights.make_weights(cfg, 1))), one_chip)
    cache = _described(jax.eval_shape(
        lambda: srv._dec.init_cache(slots)), one_chip)
    compiled = _compile_run(srv, wide, cache, one_chip)
    mem = compiled.memory_analysis()
    assert 1.2e9 < _nbytes(cache) <= mem.alias_size_in_bytes
    # 0.21 GB of temporaries: a tile of K and of V staged, the expert rows
    assert mem.temp_size_in_bytes < 0.3e9
    text = compiled.as_text()
    assert not re.findall(r"= f32\[3,16,32,128,128\]\S* copy\(", text)
    assert not re.findall(r"= f32\[(?:1,)?16,32,128,128\]", text)
    assert "ragged-dot" in text
    assert srv._dec.decode_context_rungs(max_len, slots)[0] == 1056
    assert _kv_reads(text, slots, 2, head_dim=256) == [1056]
    staged = re.findall(r"= bf16\[1,16,1056,2,256\]\{[^}]*\} fusion\(",
                        text)
    assert len(staged) == 2 and all("S(1)" in s for s in staged)


def test_qwen3_next_prefill_chunk_at_published_widths(one_chip):
    """A 4096-token chunk of a 32768-token prefill of Qwen3-Next's four
    layers at the published widths (the cell's longest bucket and its
    `prefill_chunk`): the chunked delta rule over [32, 128, 128] states,
    attention a tile of 512 queries over the bucket's keys (float32 scores
    [16, 512, 32768]: 1.1 GB), the experts grouped, and the head over ONE
    position: temporaries under 1.5 GB beside 8 GB of weights, the row's
    cache updated where it lies."""
    cfg, fam = _qwen3_next()
    dec = dataclasses.replace(fam.program.model_of(cfg), decode=True,
                              max_decode_len=32768)
    wide = _described(fam.program.program_params(jax.eval_shape(
        lambda: fam.weights.make_weights(cfg, 1))), one_chip)
    cache = _described(jax.eval_shape(lambda: dec.init_cache(1)), one_chip)
    compiled = jax.jit(dec.decode_apply, donate_argnums=(1,)).lower(
        wide, cache, jax.ShapeDtypeStruct((1, 4096), jnp.int32,
                                          sharding=one_chip)).compile()
    mem = compiled.memory_analysis()
    assert 7.9e9 < mem.argument_size_in_bytes < 8.1e9
    assert mem.temp_size_in_bytes < 1.5e9
    assert mem.alias_size_in_bytes >= _nbytes(cache)
    assert mem.output_size_in_bytes < _nbytes(cache) + 2 * 151936 * 4
    assert "ragged-dot" in compiled.as_text()


# -- the decode programs over the slot cache, at the benchmark's widths ------

_SLOTS, _MAX_LEN, _VOCAB = 28, 4096, 49152
_WIDTHS = {"3b": {"dim": 3072, "depth": 30, "heads": 24, "kv": 2},
           "7b": {"dim": 4608, "depth": 16, "heads": 36, "kv": 4}}


def _nbytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def _pool_and_shapes(widths: str, quant: bool, mesh=None):
    """A toy pool with the benchmark's head counts, its decode twin widened
    to the benchmark's widths AFTER the build (`_build_decode` reads
    ``_dec`` when called), and the shapes of the wide parameters and slot
    cache: nothing of the real size is ever allocated here."""
    w = _WIDTHS[widths]
    dt = jnp.bfloat16
    toy = TransformerLM(vocab=64, dim=w["heads"] * 8, depth=1,
                        num_heads=w["heads"], num_kv_heads=w["kv"],
                        dtype=dt, param_dtype=dt,
                        kv_cache_dtype="int8" if quant else "native")
    params = toy.init(jax.random.PRNGKey(0),
                      jnp.zeros((1, 8), jnp.int32))["params"]
    srv = DecodeServer(toy, params, slots=_SLOTS, prompt_len=64,
                       max_len=_MAX_LEN, decode_steps=4, mesh=mesh)
    srv._dec = dataclasses.replace(srv._dec, vocab=_VOCAB, dim=w["dim"],
                                   depth=w["depth"])
    flat = dataclasses.replace(srv._dec, scan_layers=False, decode=False)
    p_shapes = jax.eval_shape(lambda: stack_block_params(
        flat.init(jax.random.PRNGKey(0),
                  jnp.zeros((1, 8), jnp.int32))["params"], w["depth"]))
    return srv, p_shapes


def _described(tree, shardings):
    """The shapes of ``tree`` on described devices: one sharding for every
    leaf, or a tree of them."""
    if isinstance(shardings, jax.sharding.Sharding):
        one = shardings
        shardings = jax.tree.map(lambda _: one, tree)
    return jax.tree.map(lambda x, sh: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=sh), tree, shardings)


def _compile_run(srv, params, cache, state_shardings):
    """`DecodeServer._build_decode`'s own `run` (4 steps in a `fori_loop`
    around the layer scan) with the donation a TPU pool gives it, lowered
    over the wide shapes and the toy pool's own sampling state."""
    state = _described(
        (srv._tokens, srv._cursors, srv._remaining, srv._temps,
         srv._top_ps, srv._top_ks, srv._keys, srv._logprobs, srv._pres,
         srv._freq, srv._counts), state_shardings)
    run = srv._build_decode(4).__wrapped__
    return jax.jit(run, donate_argnums=_DECODE_DONATED).lower(
        params, state[0], cache, *state[1:]).compile()


def _whole_slice_updates(text: str, rows: int, length: int) -> list[str]:
    """The `dynamic-update-slice`s of a compiled program, fused or bare,
    whose update is a whole ``[rows, length, ...]`` slice of a cache leaf
    (leading 1s aside)."""
    shapes = dict(re.findall(r"%([\w.\-]+) = \(?(\w+\[[\d,]*\])", text))
    found = []
    for m in re.finditer(r"%([\w.\-]+) = \S+ dynamic-update-slice\("
                         r"(?:[^\s%]\S* )?%[\w.\-]+, "
                         r"(?:[^\s%]\S* )?%([\w.\-]+)",
                         text):
        dims = [int(d) for d in re.findall(r"\d+", shapes.get(
            m.group(2), "").partition("[")[2])]
        while dims and dims[0] == 1:
            dims.pop(0)
        whole = [rows, length] if rows > 1 else [length]
        if dims[:len(whole)] == whole:
            found.append(f"{m.group(1)} <- {shapes[m.group(2)]}")
    return found


def _in_place(compiled, cache_bytes: int, rows: int, temp_share=4):
    """What PR 30 holds a decode program to: the new cache IS the donated
    one, the program's own memory holds no second cache (``temp_share``
    None: a batch-1 chunk, whose scores outweigh its cache), and no
    layer's whole slice is written back."""
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= cache_bytes
    if temp_share:
        assert mem.temp_size_in_bytes < cache_bytes // temp_share
    assert not _whole_slice_updates(compiled.as_text(), rows, _MAX_LEN)


def _reads_by_the_tile(text: str, depth: int, rows: int):
    """What ISSUE 32 holds `jit_run` to: a cache leaf is read a tile of the
    context ladder at a time (`context_rungs`: a ``[1, rows, tile, kv,
    128]`` slice of the stacked leaf), and nothing in the program but a
    whole stacked leaf spans the token axis: no layer's ``[rows, 4096,
    ...]`` slice is staged or copied, and no mask or score is that long."""
    tile = context_rungs(_MAX_LEN)[0]
    assert 1 < _MAX_LEN // tile <= 8
    shapes = {tuple(int(d) for d in dims.split(","))
              for dims in re.findall(r"\w+\[([\d,]+)\]", text)}
    spans = sorted(d for d in shapes if len(d) > 2 and _MAX_LEN in d
                   and d[:3] != (depth, rows, _MAX_LEN))
    assert not spans, spans
    assert [d for d in shapes
            if len(d) == 5 and d[:3] == (1, rows, tile) and d[4] == 128]


# s8 leaves with 2 kv heads: the chip lays the argument out with the
# token axis minor (a tile holds 32 rows of a byte each: 2 heads would
# pad 16 times) and the loop with the heads minor, so the dispatch copies
# each leaf in and out: 4.2 GB of temporaries. The parent does not
# compile there at all (20.8 GB). PERF.md section 7; strict, so that the
# cure shows
_INT8_2KV = pytest.mark.xfail(
    strict=True, reason="int8 leaves with 2 kv heads are re-laid out at "
    "the dispatch's two ends (PERF.md section 7)")


@pytest.mark.parametrize("widths,quant", [
    ("3b", False), ("7b", False),
    pytest.param("3b", True, marks=_INT8_2KV), ("7b", True),
], ids=["3b-native", "7b-native", "3b-int8", "7b-int8"])
def test_decode_dispatch_updates_the_slot_cache_in_place(one_chip, widths,
                                                         quant):
    """The whole `jit_run` as `DecodeServer._build_decode` makes it (4
    steps in a `fori_loop` around the layer scan, with its donation), at
    28 slots x 4096: the cache updated where it lies, and read a tile of
    the context ladder at a time."""
    srv, p_shapes = _pool_and_shapes(widths, quant)
    cache = _described(jax.eval_shape(
        lambda: init_cache(srv._dec, _SLOTS, _MAX_LEN)), one_chip)
    compiled = _compile_run(srv, _described(p_shapes, one_chip), cache,
                            one_chip)
    _in_place(compiled, _nbytes(cache), _SLOTS)
    _reads_by_the_tile(compiled.as_text(), srv._dec.depth, _SLOTS)


@pytest.mark.parametrize("widths,quant,rows,tokens", [
    ("3b", False, _SLOTS, 4), ("7b", False, _SLOTS, 4),
    pytest.param("3b", True, _SLOTS, 4, marks=_INT8_2KV),
    ("7b", True, _SLOTS, 4),
    ("3b", False, 1, 1024), ("7b", False, 1, 1024),
    ("3b", True, 1, 1024), ("7b", True, 1, 1024),
], ids=["3b-native-chunk4", "7b-native-chunk4", "3b-int8-chunk4",
        "7b-int8-chunk4", "3b-native-prefill1024", "7b-native-prefill1024",
        "3b-int8-prefill1024", "7b-int8-prefill1024"])
def test_chunked_steps_update_the_cache_in_place(one_chip, widths, quant,
                                                 rows, tokens):
    """`decode_apply` with more than one token a row: the per-row chunk of
    4 over the pool's slots and the
    scalar-cursor 1024-token chunk of a 4096-token row (chunked
    prefill)."""
    srv, p_shapes = _pool_and_shapes(widths, quant)
    dec = dataclasses.replace(srv._dec, decode_per_row=rows > 1)
    cache = _described(jax.eval_shape(
        lambda: init_cache(dec, rows, _MAX_LEN)), one_chip)
    compiled = jax.jit(
        lambda p, c, t: decode_apply(dec, p, c, t),
        donate_argnums=(1,)).lower(
        _described(p_shapes, one_chip), cache,
        jax.ShapeDtypeStruct((rows, tokens), jnp.int32,
                             sharding=one_chip)).compile()
    _in_place(compiled, _nbytes(cache), rows,
              temp_share=4 if rows > 1 else None)


# what the parent of PR 30 compiled to on this very case: the two
# all-reduces a layer (attention out, MLP down) inside the scan; the rest
# is the embedding's and the sharded sampling tail's
_TP_COLLECTIVES = {"all-reduce": 6, "all-gather": 4, "all-to-all": 0,
                   "collective-permute": 0, "reduce-scatter": 0}


def test_tp_decode_dispatch_in_place_and_no_new_collectives(topo):
    """`jit_run` of the head-sharded pool (`n_model` 2) on two of the
    described 2x2's chips, at the 7B widths: each chip updates its half of
    the slot cache in place, and carrying the cache through the scan brings
    no collective that the parent's program did not have: no all-gather of
    a cache leaf."""
    from idunno_tpu.parallel.mesh import make_mesh
    from idunno_tpu.parallel.sharding import lm_cache_specs, lm_tp_specs

    srv, p_shapes = _pool_and_shapes(
        "7b", False, mesh=make_mesh(1, 2, devices=jax.devices()[:2]))
    mesh = make_mesh(1, 2, devices=list(topo.devices)[:2])

    def placed(tree, specs):
        return _described(tree, jax.tree.map(
            lambda sp: NamedSharding(mesh, sp), specs))
    c_shapes = jax.eval_shape(
        lambda: init_cache(srv._dec, _SLOTS, _MAX_LEN))
    cache = placed(c_shapes, lm_cache_specs(c_shapes, n_model=2))
    params = placed(p_shapes, lm_tp_specs(p_shapes, n_model=2))
    # the pool spreads its sampling state over the data axis, which has
    # one chip here: whole on both
    compiled = _compile_run(srv, params, cache,
                            NamedSharding(mesh, PartitionSpec()))
    # memory_analysis counts one device: half of the cache
    _in_place(compiled, _nbytes(cache) // 2, _SLOTS)
    text = compiled.as_text()
    _reads_by_the_tile(text, srv._dec.depth, _SLOTS)
    counts = {op: len(re.findall(rf" {op}(?:-start)?\(", text))
              for op in _TP_COLLECTIVES}
    assert counts == _TP_COLLECTIVES, counts
    assert not [line for line in text.splitlines()
                if " all-gather" in line and str(_MAX_LEN) in line]

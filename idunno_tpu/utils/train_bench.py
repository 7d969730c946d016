"""Training-path hardware bench: LM and CNN train-step throughput.

The reference is inference-only (`alexnet_resnet.py` is its whole model
layer); training is one of this framework's beyond-parity capabilities
(PARITY.md "Beyond-parity"), and like the LM serving tier it needs its own
measured hardware surface, not just CPU-mesh correctness tests:

  lm      — `engine/train_lm.py` step on a `TransformerLM`: next-token CE
            forward + backward + adamw update as ONE jitted computation,
            batch sharded over the mesh data axis. On TPU the attention is
            the REAL Pallas flash kernel fwd+bwd (``interpret=False`` —
            a kernel that fails to compile raises; no silent fallback).
            Reported as trained tokens/sec with train MFU on the standard
            6·params-FLOPs-per-token convention (fwd 2N + bwd 4N) plus the
            attention quadratic term.
  accum   — the same step with gradient accumulation (``accum_steps=2``):
            the memory/throughput trade measured, not assumed.
  fsdp    — params + optimizer state sharded over the data axis
            (`engine/train.py:fsdp_shard_train_state`, ZeRO-3 layout);
            only meaningful when the mesh has >1 device on the data axis,
            so the single-chip TPU run skips it and the CPU-mesh tests
            cover it.
  cnn     — `engine/train.py` step on ResNet-18 (the reference's model
            family): images/sec with train MFU at 3× the analytic forward
            FLOPs (the caller passes the forward number so the MFU
            denominator stays pinned to `bench.py`'s unit-tested
            functions).

Every knob is env-overridable (BENCH_TRAIN_*); `bench.py` serves the suite
as ``BENCH_SUITE=train`` with the same one-JSON-line + last-good-cache
contract as the CNN and LM suites.
"""
from __future__ import annotations

import os
import time

import jax
import jax.numpy as jnp
import numpy as np


def _env_int(name: str, default: int) -> int:
    return int(os.environ.get(name, str(default)))


def train_bench_config(platform: str) -> dict:
    """Workload sizing; TPU gets a ~0.2 B-param LM + batch-256 ResNet-18,
    other platforms a smoke-test miniature (the CPU path proves the
    machinery, not numbers)."""
    tpu = platform == "tpu"
    return {
        "dim": _env_int("BENCH_TRAIN_DIM", 1024 if tpu else 64),
        "depth": _env_int("BENCH_TRAIN_DEPTH", 12 if tpu else 1),
        "heads": _env_int("BENCH_TRAIN_HEADS", 16 if tpu else 2),
        "vocab": _env_int("BENCH_TRAIN_VOCAB", 32768 if tpu else 128),
        "seq": _env_int("BENCH_TRAIN_SEQ", 1024 if tpu else 32),
        "batch": _env_int("BENCH_TRAIN_BATCH", 8),
        "iters": _env_int("BENCH_TRAIN_ITERS", 3),
        "cnn_batch": _env_int("BENCH_TRAIN_CNN_BATCH", 256 if tpu else 8),
        "cnn_image": _env_int("BENCH_TRAIN_CNN_IMAGE", 224 if tpu else 32),
    }


def _count_params(params) -> int:
    return sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params))


def _effective_flash_blocks(seq: int) -> str:
    """The geometry `flash_attention` will actually run at this sequence
    length (kernel defaults lowered through `resolve_blocks`) — derived,
    not hardcoded, so neither a default re-tune nor a non-default
    BENCH_TRAIN_SEQ can make this provenance field lie."""
    from idunno_tpu.ops.flash_attention import resolve_blocks
    bq, bk, _ = resolve_blocks(seq)
    return f"{bq}x{bk} (kernel default resolved at seq {seq})"


def _timed_steps(step_fn, state, args: tuple, iters: int,
                 trace_name: str | None = None):
    """Compile + sync on the first call, then ``iters`` timed steps (each
    synced by a D2H read of the loss). Returns (median_s, compile_s, last_loss).
    With ``trace_name`` and BENCH_TRACE=1 one extra post-timing step runs
    under the profiler into ``.trace/<trace_name>`` (the apportionment
    evidence behind the train-MFU analysis; `benchmark/trace.py` reduces
    such a trace)."""
    t0 = time.perf_counter()
    state, metrics = step_fn(state, *args)
    loss = float(np.asarray(metrics["loss"]))
    compile_s = time.perf_counter() - t0
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        state, metrics = step_fn(state, *args)
        loss = float(np.asarray(metrics["loss"]))
        times.append(time.perf_counter() - t0)
    if trace_name and os.environ.get("BENCH_TRACE") == "1":
        from idunno_tpu.utils.tracing import trace
        root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        with trace(os.path.join(root, ".trace", trace_name)):
            _, m = step_fn(state, *args)
            float(np.asarray(m["loss"]))
    return float(np.median(times)), compile_s, loss


def run_train_bench(platform: str, device_kind: str, n_devices: int,
                    peak_bf16: float | None, *, deadline: float,
                    cnn_flops_per_image: float | None = None) -> dict:
    """One measured training record. ``deadline`` is a perf_counter() stamp
    after which optional phases (accum, fsdp, cnn) are skipped — each is a
    fresh compile; the core LM point always runs."""
    import optax

    from idunno_tpu.engine.train import (create_train_state, flat_tx,
                                         fsdp_shard_train_state,
                                         jit_train_step, shard_train_state)
    from idunno_tpu.engine.train_lm import (create_lm_train_state,
                                            jit_lm_train_step)
    from idunno_tpu.models.resnet import resnet18
    from idunno_tpu.models.transformer import TransformerLM, make_attn_fn
    from idunno_tpu.parallel.mesh import DATA_AXIS, local_mesh
    from jax.sharding import NamedSharding, PartitionSpec as P

    cfg = train_bench_config(platform)
    mesh = local_mesh()
    n_data = mesh.shape[DATA_AXIS]
    batch = -(-cfg["batch"] // n_data) * n_data    # divisible over data axis
    out: dict = {"config": dict(cfg, batch=batch),
                 "platform": platform, "device_kind": device_kind,
                 "n_devices": n_devices}

    # -- LM train step (flash fwd+bwd on TPU; loud failure, no fallback) ---
    # mixed precision: f32 params/optimizer, bf16 compute — the standard
    # training layout (serving benches use bf16 residency instead).
    attn = make_attn_fn("flash" if platform == "tpu" else "full")
    model = TransformerLM(vocab=cfg["vocab"], dim=cfg["dim"],
                          depth=cfg["depth"], num_heads=cfg["heads"],
                          causal=True, attn_fn=attn,
                          dtype=jnp.bfloat16, param_dtype=jnp.float32)
    # init through a plain-attention twin (identical param structure) at a
    # tiny seq — skips one expensive full-seq flash compile
    init_model = TransformerLM(vocab=cfg["vocab"], dim=cfg["dim"],
                               depth=cfg["depth"], num_heads=cfg["heads"],
                               causal=True,
                               dtype=jnp.bfloat16, param_dtype=jnp.float32)
    # flat layout: the traced per-tensor adamw stream was ~55% of the
    # 2026-07-31 device step (TRACE_TRAIN_LM.json); engine/train.py:flat_tx
    tx = flat_tx(optax.adamw(3e-4))
    try:
        state = create_lm_train_state(init_model, jax.random.PRNGKey(0),
                                      8, tx, batch=1)
        n_params = _count_params(state.params)
        out["n_params"] = n_params
        state = shard_train_state(state, mesh)
        tokens = jax.device_put(
            jnp.ones((batch, cfg["seq"]), jnp.int32),
            NamedSharding(mesh, P(DATA_AXIS)))
        step = jit_lm_train_step(model, tx, mesh)
        per_step, compile_s, loss = _timed_steps(
            step, state, (tokens,), cfg["iters"],
            trace_name="train_lm" if platform == "tpu" else None)
        tok_s = batch * cfg["seq"] / per_step
        out["lm"] = {
            "tokens_per_s": round(tok_s, 1),
            "batch": batch, "seq": cfg["seq"],
            "step_s": round(per_step, 4), "compile_s": round(compile_s, 2),
            "loss": round(loss, 4),
            "attention": ("flash (pallas fwd+bwd, compiled)"
                          if platform == "tpu" else "full (xla)"),
            # records at/after this field measure the flat-optimizer
            # layout; its absence marks the per-tensor-adamw era (the
            # 2026-07-31 30,499 tok/s baseline)
            "optimizer_layout": "flat (optax.flatten(adamw))",
            # record the block geometry: the FLASH_SWEEP that picked the
            # current default measured the prefill FORWARD only, so a
            # train capture at new blocks must be comparable-by-record
            # against the 128x128-era 30,499 tok/s baseline
            "flash_blocks": _effective_flash_blocks(cfg["seq"])
                            if platform == "tpu" else None,
        }
        # fwd 2N + bwd 4N per token, plus the attention quadratic term
        # (fwd 4·T·d per layer per token, ×3 with backward)
        flops_tok = (6.0 * n_params
                     + 12.0 * cfg["seq"] * cfg["dim"] * cfg["depth"])
        out["lm"]["flops_per_token_gf"] = round(flops_tok / 1e9, 6)
        if peak_bf16:
            out["lm"]["mfu"] = round(tok_s * flops_tok / peak_bf16, 4)
    except Exception as e:  # noqa: BLE001 - must record, never fall back
        out["lm"] = {"error": f"{type(e).__name__}: {e}"}
        if platform == "tpu":
            out["flash_attention"] = "FAILED_TO_COMPILE"
        return out
    out["flash_attention"] = ("compiled" if platform == "tpu"
                              else "n/a (cpu)")

    # -- gradient accumulation point --------------------------------------
    if time.perf_counter() < deadline:
        try:
            step2 = jit_lm_train_step(model, tx, mesh, accum_steps=2)
            per2, c2, _ = _timed_steps(step2, state, (tokens,), cfg["iters"])
            out["accum"] = {
                "accum_steps": 2,
                "tokens_per_s": round(batch * cfg["seq"] / per2, 1),
                "vs_plain": round(per_step / per2, 2),
                "compile_s": round(c2, 2),
            }
        except Exception as e:  # noqa: BLE001
            out["accum"] = {"error": f"{type(e).__name__}: {e}"}

    # -- FSDP (ZeRO-3) point: only meaningful with >1 device on the data
    # axis (the single-chip TPU run skips it; CPU-mesh tests cover it).
    # PER-TENSOR optimizer on purpose: ZeRO-3's point is sharded opt
    # state, and a flat [N] leaf only shards when N divides the axis —
    # so this point keeps the layout tests/test_fsdp.py covers, pays its
    # own step compile, and stamps the record (engine/train.py:flat_tx) --
    if n_data > 1 and time.perf_counter() < deadline:
        try:
            tx_pt = optax.adamw(3e-4)
            # init through the plain-attention twin at tiny seq, same as
            # the main point — re-initing with the flash model at full seq
            # would pay exactly the compile the twin exists to avoid
            fstate = create_lm_train_state(init_model, jax.random.PRNGKey(0),
                                           8, tx_pt, batch=1)
            fstate = fsdp_shard_train_state(fstate, mesh)
            fstep = jit_lm_train_step(model, tx_pt, mesh)
            perf, cf, _ = _timed_steps(fstep, fstate, (tokens,),
                                       cfg["iters"])
            out["fsdp"] = {
                "tokens_per_s": round(batch * cfg["seq"] / perf, 1),
                "vs_plain": round(per_step / perf, 2),
                "compile_s": round(cf, 2),
                "optimizer_layout":
                    "per-tensor (ZeRO-3 shards opt-state leaves)",
                "note": "vs_plain's numerator is the FLAT-layout plain "
                        "step (the shipped default) — it folds the "
                        "per-tensor layout cost in with the sharding "
                        "cost, not a same-layout A/B",
            }
        except Exception as e:  # noqa: BLE001
            out["fsdp"] = {"error": f"{type(e).__name__}: {e}"}

    # -- CNN train step (the reference's model family) ---------------------
    if time.perf_counter() < deadline:
        try:
            cb = -(-cfg["cnn_batch"] // n_data) * n_data
            size = cfg["cnn_image"]
            cnn = resnet18()
            ctx = flat_tx(optax.sgd(0.1, momentum=0.9))
            # global-avg-pool makes param shapes size-independent: init at
            # 64px to keep the init compile cheap
            cstate = create_train_state(cnn, jax.random.PRNGKey(0),
                                        min(size, 64), ctx, batch=1)
            cstate = shard_train_state(cstate, mesh)
            bspec = NamedSharding(mesh, P(DATA_AXIS))
            images = jax.device_put(
                jnp.zeros((cb, size, size, 3), jnp.float32), bspec)
            labels = jax.device_put(jnp.zeros((cb,), jnp.int32), bspec)
            cstep = jit_train_step(cnn, ctx, mesh)
            perc, cc, closs = _timed_steps(
                cstep, cstate, (images, labels), cfg["iters"],
                trace_name="train_cnn" if platform == "tpu" else None)
            ips = cb / perc
            out["cnn"] = {
                "model": "resnet18", "images_per_s": round(ips, 1),
                "batch": cb, "image_size": size,
                "step_s": round(perc, 4), "compile_s": round(cc, 2),
                "loss": round(closs, 4),
                "optimizer_layout": "flat (optax.flatten(sgd+momentum))",
            }
            if peak_bf16 and cnn_flops_per_image:
                out["cnn"]["mfu"] = round(
                    ips * 3.0 * cnn_flops_per_image / peak_bf16, 4)
        except Exception as e:  # noqa: BLE001
            out["cnn"] = {"error": f"{type(e).__name__}: {e}"}

    return out

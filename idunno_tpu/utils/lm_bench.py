"""LM-tier hardware bench: prefill + decode tokens/sec on the live backend.

The reference has no autoregressive tier at all (`alexnet_resnet.py` is its
whole model layer); this framework's LM serving stack is roughly half the
codebase, so it carries its own measured surface (round-3 VERDICT weak #3):

  prefill   — a jitted full forward at [B, T] through the REAL Pallas flash
              attention kernel on TPU (``interpret=False`` — a kernel that
              fails to compile raises; there is no silent XLA fallback here),
              reported as prefill tokens/sec.
  decode    — `DecodeServer` steady state: all slots live, ``decode_steps``
              fused tokens per dispatch, timed over K dispatches after the
              compile + admission phases. Decode is HBM-bound, so alongside
              decode MFU (2·params FLOPs/token convention) the record carries
              the implied weight-stream bandwidth — the honest utilization
              axis for this phase.
  int8      — the same steady-state decode with int8 weight-only residency
              (`ops/quantize.py`): decode re-reads every weight per step, so
              residency is the lever.
  gqa       — the same decode with `num_kv_heads` < heads (grouped-query
              attention): the KV cache shrinks by the group factor; the
              record carries both models' param counts so the weight-side
              saving is separable from the cache saving.
  flash_bwd — the custom-VJP Pallas backward kernels compiled + timed
              (TPU only, and only when the forward built).

Every knob is env-overridable (BENCH_LM_*); `bench.py` embeds the compact
record in the default run and serves the full suite as ``BENCH_SUITE=lm``.
"""
from __future__ import annotations

import os
import time

import jax
import jax.numpy as jnp
import numpy as np


def _env_int(name: str, default: int) -> int:
    return int(os.environ.get(name, str(default)))


def lm_bench_config(platform: str) -> dict:
    """Model/workload sizing; TPU gets a ~0.2 B-param serving config, other
    platforms a smoke-test miniature (the CPU path exists to prove the
    machinery, not to claim numbers)."""
    tpu = platform == "tpu"
    return {
        "dim": _env_int("BENCH_LM_DIM", 1024 if tpu else 128),
        "depth": _env_int("BENCH_LM_DEPTH", 12 if tpu else 2),
        "heads": _env_int("BENCH_LM_HEADS", 16 if tpu else 4),
        "vocab": _env_int("BENCH_LM_VOCAB", 32768 if tpu else 512),
        # Decode slots/steps are sized so one dispatch carries enough work
        # to amortize a fixed per-dispatch latency against the HBM-bound
        # weight stream; whether 128 steps still pay on a locally
        # attached chip is the benchmark PR's measurement.
        "slots": _env_int("BENCH_LM_SLOTS", 16 if tpu else 4),
        "prompt_len": _env_int("BENCH_LM_PROMPT", 64 if tpu else 16),
        "max_new": _env_int("BENCH_LM_MAXNEW", 448 if tpu else 48),
        "max_len": _env_int("BENCH_LM_MAXLEN", 512 if tpu else 128),
        "decode_steps": _env_int("BENCH_LM_DECODE_STEPS", 128 if tpu else 8),
        "prefill_batch": _env_int("BENCH_LM_PREFILL_BATCH", 4 if tpu else 2),
        "prefill_seq": _env_int("BENCH_LM_PREFILL_SEQ", 1024 if tpu else 64),
        # scan-tiled prefill dispatches (the CNN sweep's BENCH_SCAN_TILE
        # analog): tile full prefill batches per timed dispatch
        "prefill_tile": _env_int("BENCH_LM_PREFILL_TILE", 4 if tpu else 1),
        # full-suite GQA comparison point: same model with this many K/V
        # heads (must divide heads; 0 disables the point)
        "gqa_kv_heads": _env_int("BENCH_LM_GQA_KV_HEADS", 4 if tpu else 1),
    }


def _count_params(params) -> tuple[int, int]:
    """(n_params, bytes) over a params tree."""
    leaves = jax.tree.leaves(params)
    n = sum(int(np.prod(l.shape)) for l in leaves)
    b = sum(int(np.prod(l.shape)) * l.dtype.itemsize for l in leaves)
    return n, b


def timed_prefill_dispatch(model, params, tiled_toks) -> tuple[float, float]:
    """(median seconds per scan-TILED prefill dispatch, compile seconds).
    The single timing protocol for prefill points — the suite's prefill
    phase AND tools/flash_sweep.py both call this, so a methodology tweak
    (sync read, median count, tiling) can never make their numbers
    silently incomparable."""
    f = jax.jit(lambda p, xs: jax.lax.scan(
        lambda c, x: (c, model.apply({"params": p}, x)), None, xs)[1])
    t0 = time.perf_counter()
    np.asarray(f(params, tiled_toks)[0, 0, 0, 0])      # compile + sync
    c_s = time.perf_counter() - t0
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        np.asarray(f(params, tiled_toks)[0, 0, 0, 0])
        times.append(time.perf_counter() - t0)
    return float(np.median(times)), c_s


def prefill_flops_per_token(n_params: int, seq: int, dim: int,
                            depth: int) -> float:
    """Forward ≈ 2·params FLOPs/token + the attention quadratic term —
    shared MFU denominator for the suite and the flash sweep."""
    return 2.0 * n_params + 4.0 * seq * dim * depth


def _steady_decode_tok_s(srv, cfg: dict) -> tuple[float, int, float]:
    """Fill every slot, then time K full-occupancy dispatches. Each
    `step()` ends in a host D2H read of the remaining counters
    (`_retire_finished`), so per-step timing is naturally synced. Returns
    (tokens/sec, K, seconds/dispatch) — the last makes the fixed
    per-dispatch latency separable from the HBM-bound compute."""
    for _ in range(srv.slots):
        srv.submit(list(range(1, cfg["prompt_len"] + 1)),
                   max_new=cfg["max_new"])
    srv.step()                       # admission + first dispatch (all live)
    k = max(1, (cfg["max_new"] - 1) // cfg["decode_steps"] - 1)
    t0 = time.perf_counter()
    for _ in range(k):
        srv.step()
    dt = time.perf_counter() - t0
    return srv.slots * cfg["decode_steps"] * k / dt, k, dt / k


def run_lm_bench(platform: str, device_kind: str, n_devices: int,
                 peak_bf16: float | None, *, deadline: float,
                 compact: bool = False) -> dict:
    """One measured LM record. ``deadline`` is a perf_counter() stamp after
    which optional phases are skipped (each phase is a fresh compile).
    ``compact`` drops the int8 and gqa phases
    (the unattended default run embeds the compact record; BENCH_SUITE=lm
    runs everything)."""
    from idunno_tpu.engine.serve_lm import DecodeServer
    from idunno_tpu.models.transformer import TransformerLM, make_attn_fn

    cfg = lm_bench_config(platform)
    out: dict = {"config": {k: v for k, v in cfg.items()},
                 "platform": platform, "device_kind": device_kind,
                 "n_devices": n_devices}
    dt = jnp.bfloat16
    model = TransformerLM(vocab=cfg["vocab"], dim=cfg["dim"],
                          depth=cfg["depth"], num_heads=cfg["heads"],
                          causal=True, dtype=dt, param_dtype=dt)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    n_params, param_bytes = _count_params(params)
    out["n_params"] = n_params
    out["param_bytes"] = param_bytes

    # -- prefill through the real attention kernel -----------------------
    # On TPU this IS the Pallas flash kernel, interpret=False: if it cannot
    # compile, the phase records the error loudly instead of falling back.
    # The timed region scan-tiles `tile` full prefill batches into ONE
    # dispatch (distinct token buffers — no CSE), the same amortization
    # the CNN sweep uses: a fixed per-dispatch latency of the same order
    # as one prefill's compute would cap the measured prefill MFU.
    b, t = cfg["prefill_batch"], cfg["prefill_seq"]
    tile = max(1, cfg["prefill_tile"])
    tiled_toks = jnp.asarray(
        np.random.default_rng(0).integers(
            1, cfg["vocab"], size=(tile, b, t)), jnp.int32)

    def timed_prefill(m):
        return timed_prefill_dispatch(m, params, tiled_toks)

    try:
        # kernel defaults are the 2026-08-01 FLASH_SWEEP.json winner
        # (256x1024); BENCH_LM_FLASH_BQ/BK override per-key for re-sweeps
        # — an unset key genuinely inherits the kernel signature default
        fkw = {}
        if os.environ.get("BENCH_LM_FLASH_BQ"):
            fkw["block_q"] = _env_int("BENCH_LM_FLASH_BQ", 0)
        if os.environ.get("BENCH_LM_FLASH_BK"):
            fkw["block_k"] = _env_int("BENCH_LM_FLASH_BK", 0)
        attn = (make_attn_fn("flash", **fkw) if platform == "tpu"
                else make_attn_fn("full"))
        fwd_model = TransformerLM(vocab=cfg["vocab"], dim=cfg["dim"],
                                  depth=cfg["depth"], num_heads=cfg["heads"],
                                  causal=True, attn_fn=attn,
                                  dtype=dt, param_dtype=dt)
        pre_s, compile_s = timed_prefill(fwd_model)
        out["prefill"] = {
            "tokens_per_s": round(tile * b * t / pre_s, 1),
            "batch": b, "seq": t, "scan_tile": tile,
            "compile_s": round(compile_s, 2),
            "attention": ("flash (pallas, compiled)" if platform == "tpu"
                          else "full (xla; flash needs tpu)"),
        }
        if platform == "tpu":
            # the geometry that actually ran (env override or kernel
            # default, lowered through resolve_blocks) — without this an
            # overridden capture is indistinguishable from a default one
            from idunno_tpu.ops.flash_attention import resolve_blocks
            ebq, ebk, _ = resolve_blocks(t, **fkw) if fkw \
                else resolve_blocks(t)
            out["prefill"]["flash_blocks"] = f"{ebq}x{ebk}"
        if peak_bf16:
            flops_tok = prefill_flops_per_token(
                n_params, t, cfg["dim"], cfg["depth"])
            out["prefill"]["mfu"] = round(
                (tile * b * t / pre_s) * flops_tok / peak_bf16, 4)
        # flash must EARN its place vs stock XLA attention on the same
        # shapes (full suite only: one extra compile)
        if platform == "tpu" and not compact and \
                time.perf_counter() < deadline:
            try:
                full_model = TransformerLM(
                    vocab=cfg["vocab"], dim=cfg["dim"], depth=cfg["depth"],
                    num_heads=cfg["heads"], causal=True,
                    attn_fn=make_attn_fn("full"),
                    dtype=dt, param_dtype=dt)
                full_s, full_c = timed_prefill(full_model)
                out["prefill"]["xla_full_attention"] = {
                    "tokens_per_s": round(tile * b * t / full_s, 1),
                    "flash_speedup": round(full_s / pre_s, 2),
                    "compile_s": round(full_c, 2),
                }
            except Exception as e:  # noqa: BLE001
                out["prefill"]["xla_full_attention"] = {
                    "error": f"{type(e).__name__}: {e}"}
    except Exception as e:  # noqa: BLE001 - must record, never fall back
        out["prefill"] = {"error": f"{type(e).__name__}: {e}"}
        if platform == "tpu":
            out["flash_attention"] = "FAILED_TO_COMPILE"
    if "error" not in out.get("prefill", {}):
        out["flash_attention"] = ("compiled" if platform == "tpu"
                                  else "n/a (cpu)")

    # flash BACKWARD (custom VJP, its own Pallas kernels): the training
    # path must also compile on real hardware — fwd compiling says nothing
    # about the dq/dk/dv kernels (round-3 VERDICT weak #3). Only attempted
    # when the forward phase built — a forward failure must not be
    # recorded as the backward kernels failing.
    if (platform == "tpu" and time.perf_counter() < deadline
            and "error" not in out.get("prefill", {})):
        try:
            def loss(p, x):
                return fwd_model.apply({"params": p}, x).mean()

            gfn = jax.jit(jax.grad(loss))
            b2, t2 = max(1, cfg["prefill_batch"] // 2), cfg["prefill_seq"]
            toks2 = jnp.ones((b2, t2), jnp.int32)

            def sync(tree):          # a D2H read forces completion
                leaf = jax.tree.leaves(tree)[0]
                np.asarray(leaf.reshape(-1)[0])

            t0 = time.perf_counter()
            sync(gfn(params, toks2))
            c_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            sync(gfn(params, toks2))
            out["flash_bwd"] = {
                "status": "compiled",
                "batch": b2, "seq": t2,
                "compile_s": round(c_s, 2),
                "step_s": round(time.perf_counter() - t0, 4),
            }
        except Exception as e:  # noqa: BLE001
            out["flash_bwd"] = {"status": "FAILED_TO_COMPILE",
                                "error": f"{type(e).__name__}: {e}"}

    # -- steady-state decode ----------------------------------------------
    def measure_pool(m, p, slots=None, trace_name=None, **server_kw):
        """Build a pool, pay its compiles on a warm-up request, then
        measure steady-state decode tokens/sec — the shared protocol for
        the plain/int8/GQA/slot-scaling points. Returns (tok/s, timed
        dispatches, seconds/dispatch, compile seconds). With
        ``trace_name`` and BENCH_TRACE=1, one extra post-timing dispatch
        runs under the profiler into ``.trace/<trace_name>`` (the decode
        trace→apportion→fix loop; `benchmark/trace.py` reduces such a
        trace)."""
        srv = DecodeServer(m, p, slots=slots or cfg["slots"],
                           prompt_len=cfg["prompt_len"],
                           max_len=cfg["max_len"],
                           decode_steps=cfg["decode_steps"], **server_kw)
        c_s = srv.warmup()
        ts, kk, disp_s = _steady_decode_tok_s(srv, cfg)
        if trace_name and os.environ.get("BENCH_TRACE") == "1":
            from idunno_tpu.utils.tracing import trace
            root = os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))))
            with trace(os.path.join(root, ".trace", trace_name)):
                srv.step()        # rows still live: k leaves budget over
        return ts, kk, disp_s, c_s

    tok_s, k, dispatch_s, compile_s = measure_pool(
        model, params, trace_name="lm_decode" if platform == "tpu" else None)
    out["decode_compile_s"] = round(compile_s, 2)
    out["decode"] = {
        "tokens_per_s": round(tok_s, 1),
        "slots": cfg["slots"], "decode_steps": cfg["decode_steps"],
        "timed_dispatches": k, "dispatch_s": round(dispatch_s, 4),
        # decode re-streams the whole weight set once per token step
        # (all slots advance together): steps/s = tok_s / slots
        "implied_weight_stream_gbps": round(
            param_bytes * (tok_s / cfg["slots"]) / 1e9, 1),
    }
    if peak_bf16:
        out["decode"]["mfu"] = round(tok_s * 2.0 * n_params / peak_bf16, 4)

    # -- int8 residency (full suite only) ---------------------------------
    if not compact and time.perf_counter() < deadline:
        try:
            tok8, _, _, _ = measure_pool(model, params, quantize="int8")
            out["int8_decode"] = {
                "tokens_per_s": round(tok8, 1),
                "vs_bf16": round(tok8 / tok_s, 2),
            }
        except Exception as e:  # noqa: BLE001
            out["int8_decode"] = {"error": f"{type(e).__name__}: {e}"}

    # GQA decode point after int8 (a new phase must never eat the budget
    # of a previously-established surface — later phases sacrifice first,
    # so the newest, decode_slots_scaling, runs LAST): same arch with fewer
    # K/V heads. The cache shrinks by the group factor; the K/V
    # projections also shrink (params_* fields expose the weight-side
    # confound), so vs_mha bundles cache bandwidth + weight streaming.
    kvh = cfg["gqa_kv_heads"]
    if (not compact and kvh and kvh != cfg["heads"]
            and cfg["heads"] % kvh == 0
            and time.perf_counter() < deadline):
        try:
            gq_model = TransformerLM(
                vocab=cfg["vocab"], dim=cfg["dim"], depth=cfg["depth"],
                num_heads=cfg["heads"], num_kv_heads=kvh,
                causal=True, dtype=dt, param_dtype=dt)
            gq_params = gq_model.init(
                jax.random.PRNGKey(2),
                jnp.zeros((1, 8), jnp.int32))["params"]
            gq_n, _ = _count_params(gq_params)
            tokg, _, _, _ = measure_pool(gq_model, gq_params)
            out["gqa_decode"] = {
                "kv_heads": kvh, "heads": cfg["heads"],
                "tokens_per_s": round(tokg, 1),
                "vs_mha": round(tokg / tok_s, 2),
                "params_mha": n_params, "params_gqa": gq_n,
                "kv_cache_bytes_per_slot": int(
                    2 * cfg["max_len"] * kvh
                    * (cfg["dim"] // cfg["heads"]) * 2 * cfg["depth"]),
            }
        except Exception as e:  # noqa: BLE001
            out["gqa_decode"] = {"error": f"{type(e).__name__}: {e}"}

    # decode slot-scaling point: the base-slots decode streams weights at
    # a fraction of HBM peak (64 of 819 GB/s, 2026-07-31 capture) — the
    # per-step cost is op-dispatch bound, not bandwidth bound, so tok/s
    # should rise near-linearly with slots until the weight stream
    # saturates. 4x slots, same weight traffic per step: this point
    # measures the serving throughput actually available at depth.
    if not compact and time.perf_counter() < deadline:
        try:
            big = cfg["slots"] * 4
            tokb, _, disp_b, _ = measure_pool(model, params, slots=big)
            out["decode_slots_scaling"] = {
                "slots": big,
                "tokens_per_s": round(tokb, 1),
                "vs_base_slots": round(tokb / tok_s, 2),
                "dispatch_s": round(disp_b, 4),
                "implied_weight_stream_gbps": round(
                    param_bytes * (tokb / big) / 1e9, 1),
            }
        except Exception as e:  # noqa: BLE001
            out["decode_slots_scaling"] = {"error": f"{type(e).__name__}: {e}"}

    return out


def lm_slots_candidates(platform: str) -> list[int]:
    """Slot counts for the BENCH_SUITE=lm_slots scaling curve. TPU sweeps
    the serving-relevant 16/32/64 ladder; CPU proves the machinery on a
    miniature ladder. BENCH_LM_SLOTS_CURVE=a,b,c overrides."""
    env = os.environ.get("BENCH_LM_SLOTS_CURVE")
    if env:
        return [int(x) for x in env.split(",") if x.strip()]
    return [16, 32, 64] if platform == "tpu" else [2, 4, 8]


def bless_slots(curve: list[dict], frac: float | None = None) -> dict:
    """Pick the slot default from a measured curve: the SMALLEST slot
    count whose throughput reaches ``frac`` (default 0.5, overridable via
    BENCH_LM_SLOTS_BLESS_FRAC) of the curve's max. Rationale: decode
    throughput rises sub-linearly with slots (the weight stream is shared)
    while KV-cache HBM and per-request latency grow linearly — once a
    point clears half the attainable throughput, doubling slots buys
    little throughput for double the footprint. Pure function of the
    record so the test pins it on a synthetic curve."""
    if frac is None:
        frac = float(os.environ.get("BENCH_LM_SLOTS_BLESS_FRAC", "0.5"))
    best = max(r["tokens_per_s"] for r in curve)
    pick = min((r for r in curve if r["tokens_per_s"] >= frac * best),
               key=lambda r: r["slots"])
    return {"slots": pick["slots"], "frac_of_max": round(
                pick["tokens_per_s"] / best, 3),
            "rule": f"smallest slots with tok/s >= {frac:g} x max"}


def run_lm_slots_bench(platform: str, device_kind: str, n_devices: int,
                       peak_bf16: float | None, *, deadline: float,
                       compact: bool = False) -> dict:
    """BENCH_SUITE=lm_slots: the decode slot-scaling CURVE (run_lm_bench
    measures one extra 4x point; this suite owns the full ladder) plus a
    blessed serving default derived from it. Each point is the shared
    measure-pool protocol: build, `warmup()` (compile paid + accounting
    reset), then timed full-occupancy dispatches. Points past the first
    are dropped (and recorded as skipped) when the deadline hits."""
    from idunno_tpu.engine.serve_lm import DecodeServer
    from idunno_tpu.models.transformer import TransformerLM

    cfg = lm_bench_config(platform)
    out: dict = {"config": {k: v for k, v in cfg.items()},
                 "platform": platform, "device_kind": device_kind,
                 "n_devices": n_devices}
    dt = jnp.bfloat16
    model = TransformerLM(vocab=cfg["vocab"], dim=cfg["dim"],
                          depth=cfg["depth"], num_heads=cfg["heads"],
                          causal=True, dtype=dt, param_dtype=dt)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    n_params, param_bytes = _count_params(params)
    out["n_params"] = n_params
    head_dim = cfg["dim"] // cfg["heads"]
    curve: list[dict] = []
    skipped: list[int] = []
    for s in lm_slots_candidates(platform):
        if curve and time.perf_counter() > deadline:
            skipped.append(s)
            continue
        try:
            srv = DecodeServer(model, params, slots=s,
                               prompt_len=cfg["prompt_len"],
                               max_len=cfg["max_len"],
                               decode_steps=cfg["decode_steps"])
            c_s = srv.warmup()
            ts, k, disp_s = _steady_decode_tok_s(srv, cfg)
            point = {
                "slots": s,
                "tokens_per_s": round(ts, 1),
                "per_slot_tok_s": round(ts / s, 1),
                "dispatch_s": round(disp_s, 4),
                "timed_dispatches": k,
                "compile_s": round(c_s, 2),
                # every step streams the full weight set once, shared by
                # all slots: steps/s = tok_s / slots
                "implied_weight_stream_gbps": round(
                    param_bytes * (ts / s) / 1e9, 1),
                # bf16 K+V for every slot's full max_len window — the
                # linear cost the bless rule weighs against throughput
                "kv_cache_bytes": int(2 * s * cfg["max_len"]
                                      * cfg["heads"] * head_dim * 2
                                      * cfg["depth"]),
            }
            if peak_bf16:
                point["mfu"] = round(ts * 2.0 * n_params / peak_bf16, 4)
            curve.append(point)
            del srv
        except Exception as e:  # noqa: BLE001 - record, never fall back
            curve.append({"slots": s, "error": f"{type(e).__name__}: {e}"})
    ok = [r for r in curve if "error" not in r]
    out["slots_curve"] = curve
    if skipped:
        out["skipped_slots"] = skipped      # no silent truncation
    if ok:
        best = max(ok, key=lambda r: r["tokens_per_s"])
        out["blessed"] = bless_slots(ok)
        # headline of the `BENCH_SUITE=lm_slots` record (bench.py's
        # _run_record_suite reads out[value_key]["tokens_per_s"])
        out["best"] = {"slots": best["slots"],
                       "tokens_per_s": best["tokens_per_s"]}
    return out


def prefix_bench_workload(cfg: dict, block_size: int
                          ) -> tuple[list[list[int]], int, tuple[int, ...]]:
    """(prompts, shared_prefix_len, prompt_buckets) for the shared-prefix
    serving workload: ``3·slots`` full-length prompts sharing a block-
    aligned head of ~3/4 prompt_len (a system/few-shot prompt) with
    unique tails. The bucket ladder lets a radix hit prefill only its
    tail at the small bucket — the FLOPs the cache exists to skip — while
    the cache-off pool pays the full bucket every admission. Single
    source of truth for the bench phase and its CPU record-shape test."""
    pl = cfg["prompt_len"]
    shared_len = max(block_size, (pl * 3 // 4) // block_size * block_size)
    if shared_len >= pl:
        shared_len = max(0, pl - block_size)
    buckets = tuple(sorted({pl, max(1, pl // 2), max(1, pl - shared_len)}))
    rng = np.random.default_rng(7)
    head = [int(t) for t in rng.integers(1, cfg["vocab"], size=shared_len)]
    prompts = []
    for _ in range(cfg["slots"] * 3):
        tail = [int(t) for t in rng.integers(1, cfg["vocab"],
                                             size=pl - shared_len)]
        prompts.append(head + tail)
    return prompts, shared_len, buckets


def run_lm_prefix_bench(platform: str, device_kind: str, n_devices: int,
                        peak_bf16: float | None, *, deadline: float,
                        compact: bool = False) -> dict:
    """BENCH_SUITE=lm_prefix: the shared-prefix serving workload through
    the paged KV block pool + radix prefix cache (`engine/kv_blocks.py`,
    `serve/prefix_cache.py`), cache-on vs cache-off on the SAME pool
    config. The comparable pair is (tokens/sec to drain, admission
    prefill tokens actually computed): the cache turns each admission's
    full-bucket prefill into a tail-bucket prefill after a block-aligned
    radix hit, token-exactly. ``cache_on`` is the headline record
    (`BENCH_SUITE=lm_prefix`)."""
    from idunno_tpu.engine.serve_lm import DecodeServer
    from idunno_tpu.models.transformer import TransformerLM

    cfg = lm_bench_config(platform)
    tpu = platform == "tpu"
    block = _env_int("BENCH_LM_KV_BLOCK", 16 if tpu else 4)
    out: dict = {"config": {k: v for k, v in cfg.items()},
                 "platform": platform, "device_kind": device_kind,
                 "n_devices": n_devices, "kv_block_size": block}
    dt = jnp.bfloat16
    model = TransformerLM(vocab=cfg["vocab"], dim=cfg["dim"],
                          depth=cfg["depth"], num_heads=cfg["heads"],
                          causal=True, dtype=dt, param_dtype=dt)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    n_params, param_bytes = _count_params(params)
    out["n_params"] = n_params
    out["param_bytes"] = param_bytes

    prompts, shared_len, buckets = prefix_bench_workload(cfg, block)
    max_new = min(cfg["decode_steps"] + 1,
                  cfg["max_len"] - cfg["prompt_len"])
    out["workload"] = {"n_requests": len(prompts),
                       "shared_prefix_len": shared_len,
                       "prompt_len": cfg["prompt_len"],
                       "prompt_buckets": list(buckets),
                       "max_new": max_new}

    def run_pool(**server_kw) -> dict:
        srv = DecodeServer(model, params, slots=cfg["slots"],
                           prompt_len=cfg["prompt_len"],
                           max_len=cfg["max_len"],
                           decode_steps=cfg["decode_steps"],
                           prompt_buckets=buckets, **server_kw)
        # warm-up pays every compile the timed region will hit: the
        # first request compiles the cold full-bucket path (and, cache-
        # on, seeds the tree); the second compiles the hit path (tail
        # bucket + spliced radix prefix)
        for _ in range(2):
            srv.submit(prompts[0], max_new=2)
            srv.run_until_drained()
        s0 = srv.stats()
        t0 = time.perf_counter()
        for p in prompts:
            srv.submit(p, max_new=max_new)
        srv.run_until_drained()
        drain_s = time.perf_counter() - t0
        s1 = srv.stats()
        gen = s1["tokens_generated"] - s0["tokens_generated"]
        rec = {
            "tokens_per_s": round(gen / drain_s, 1),
            "drain_s": round(drain_s, 3),
            "tokens_generated": gen,
            "prefill_tokens": s1["prefill_tokens"] - s0["prefill_tokens"],
            "dispatches": s1["dispatches"] - s0["dispatches"],
        }
        if "prefix_cache" in s1:
            rec["prefix_cache"] = s1["prefix_cache"]
        return rec

    # headline first: a deadline hit must cost the baseline, not the
    # cache-on record the suite exists to capture. Pool sized one chain
    # above peak pinned capacity so the shared head isn't competing
    # with live chains for blocks.
    per_chain = -(-cfg["prompt_len"] // block)
    out["cache_on"] = run_pool(
        kv_block_size=block,
        kv_cache_blocks=(cfg["slots"] + 1) * per_chain)
    if time.perf_counter() < deadline:
        try:
            out["cache_off"] = run_pool()
            on, off = out["cache_on"], out["cache_off"]
            out["speedup_vs_off"] = round(
                on["tokens_per_s"] / off["tokens_per_s"], 2)
            out["prefill_tokens_ratio"] = round(
                on["prefill_tokens"] / max(off["prefill_tokens"], 1), 3)
        except Exception as e:  # noqa: BLE001
            out["cache_off"] = {"error": f"{type(e).__name__}: {e}"}
    if peak_bf16:
        out["cache_on"]["mfu"] = round(
            out["cache_on"]["tokens_per_s"] * 2.0 * n_params / peak_bf16,
            4)
    return out


class _LocalRing:
    """In-process stand-in for `FileStoreService`'s client surface with
    the semantics the cluster prefix cache leans on (monotone versions
    past tombstones, typed StoreError misses) plus byte counters. The
    suite measures the PREFILL COMPUTE a remote chain saves a replica —
    store transport cost is a cluster property the chaos/cluster tests
    own, not this single-process bench."""

    def __init__(self):
        from idunno_tpu.store.sdfs import StoreError
        self._miss = StoreError
        self.blobs: dict[str, tuple[bytes, int]] = {}
        self.tombs: dict[str, int] = {}
        self.bytes_put = 0
        self.bytes_got = 0

    def put_bytes(self, name, blob):
        v = max(self.blobs.get(name, (b"", 0))[1],
                self.tombs.get(name, 0)) + 1
        self.blobs[name] = (bytes(blob), v)
        self.bytes_put += len(blob)
        return v

    def get_bytes(self, name, version=None):
        if name not in self.blobs:
            raise self._miss(f"{name}: not found")
        blob, v = self.blobs[name]
        self.bytes_got += len(blob)
        return blob, v

    def stat(self, name):
        if name not in self.blobs:
            raise self._miss(f"{name}: not found")
        return self.blobs[name][1], ("local",)

    def delete(self, name):
        if name in self.blobs:
            self.tombs[name] = self.blobs.pop(name)[1]


def run_lm_cluster_prefix_bench(platform: str, device_kind: str,
                                n_devices: int, peak_bf16: float | None,
                                *, deadline: float,
                                compact: bool = False) -> dict:
    """BENCH_SUITE=lm_cluster_prefix: what a PUBLISHED KV chain buys a
    replica that never served the prompt family (ISSUE 17). One
    publisher pool serves the shared-prefix workload and publishes its
    block chains content-addressed into the ring; then the first-request
    TTFT of three fresh replicas is measured on the SAME family:
    ``baseline`` (no cluster tier — full-bucket prefill), ``cold``
    (cluster tier on — the admission probes the ring, fetches the chain
    and prefills only the suffix) and ``warmed`` (``prefix_warm`` runs
    first, as the autoscaler does at spawn, so the fetch is off the
    request's critical path). Headline is the warmed replica's drain
    throughput; ``suffix_prefill_fraction`` — the share of prompt
    tokens the remote hit did NOT prefill — is the structural win."""
    from idunno_tpu.engine.serve_lm import DecodeServer
    from idunno_tpu.models.transformer import TransformerLM
    from idunno_tpu.serve.cluster_prefix import ClusterPrefixCache

    cfg = lm_bench_config(platform)
    tpu = platform == "tpu"
    block = _env_int("BENCH_LM_KV_BLOCK", 16 if tpu else 4)
    out: dict = {"config": {k: v for k, v in cfg.items()},
                 "platform": platform, "device_kind": device_kind,
                 "n_devices": n_devices, "kv_block_size": block}
    dt = jnp.bfloat16
    model = TransformerLM(vocab=cfg["vocab"], dim=cfg["dim"],
                          depth=cfg["depth"], num_heads=cfg["heads"],
                          causal=True, dtype=dt, param_dtype=dt)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    n_params, _ = _count_params(params)
    out["n_params"] = n_params

    prompts, shared_len, buckets = prefix_bench_workload(cfg, block)
    max_new = min(cfg["decode_steps"] + 1,
                  cfg["max_len"] - cfg["prompt_len"])
    out["workload"] = {"n_requests": len(prompts),
                       "shared_prefix_len": shared_len,
                       "prompt_len": cfg["prompt_len"],
                       "prompt_buckets": list(buckets),
                       "max_new": max_new}
    ring = _LocalRing()
    per_chain = -(-cfg["prompt_len"] // block)
    pool_kw = dict(slots=cfg["slots"], prompt_len=cfg["prompt_len"],
                   max_len=cfg["max_len"], decode_steps=cfg["decode_steps"],
                   prompt_buckets=buckets, kv_block_size=block,
                   kv_cache_blocks=(cfg["slots"] + 1) * per_chain)

    def replica(cluster: bool, salt: int = 0) -> DecodeServer:
        srv = DecodeServer(model, params, **pool_kw)
        if cluster:
            srv.cluster_prefix = ClusterPrefixCache(
                ring, "bench-cluster", block, publish_min_hits=0)
        # pay every compile the timed region will hit on a DISJOINT
        # prompt family (per-replica salted, so it can't collide with
        # the workload's shared head OR another replica's published
        # warm-up chain): cold full-bucket path first, then the radix-
        # hit tail path — a remote graft prefills through the same
        # spliced computation a local hit does, so both measured paths
        # are warm after this
        warm = [(t + i + 7 * salt) % cfg["vocab"] or 1
                for i, t in enumerate(prompts[0])]
        for _ in range(2):
            srv.submit(warm, max_new=2)
            srv.run_until_drained()
        return srv

    def first_request(srv, p) -> dict:
        s0 = srv.stats()
        t0 = time.perf_counter()
        srv.submit(p, max_new=1)
        srv.run_until_drained()
        ttft = time.perf_counter() - t0
        s1 = srv.stats()
        return {"ttft_s": round(ttft, 4),
                "prefill_tokens": (s1["prefill_tokens"]
                                   - s0["prefill_tokens"])}

    # publisher: serving the family publishes its chains into the ring
    pub = replica(cluster=True)
    for p in prompts:
        pub.cluster_prefix.note(p, "bench")
        pub.submit(p, max_new=max_new)
    pub.run_until_drained()
    pcs = pub.prefix_cache_stats()
    out["publisher"] = {
        "published_chains": pcs["prefix_published_chains"],
        "ring_blobs": len(ring.blobs),
        "ring_bytes": ring.bytes_put}

    # three fresh replicas, same first request from the published family
    out["baseline"] = first_request(replica(cluster=False, salt=1),
                                    prompts[1])
    cold = replica(cluster=True, salt=2)
    out["cold"] = first_request(cold, prompts[2])
    out["cold"].update({k: v for k, v in cold.prefix_cache_stats().items()
                        if k.startswith("prefix_")})
    warmed = replica(cluster=True, salt=3)
    t0 = time.perf_counter()
    wres = warmed.prefix_warm(tenant="bench")
    warm_s = time.perf_counter() - t0
    out["warmed"] = first_request(warmed, prompts[3])
    out["warmed"].update(
        warm_s=round(warm_s, 4),
        warm_blocks=int(wres.get("fetched_blocks", 0)))
    # the structural win: prompt tokens the remote hit did NOT prefill
    # on the replica's first request (block-truncated, never negative)
    out["suffix_prefill_fraction"] = round(
        1.0 - out["warmed"]["prefill_tokens"] / cfg["prompt_len"], 3)
    out["cold_suffix_prefill_fraction"] = round(
        1.0 - out["cold"]["prefill_tokens"] / cfg["prompt_len"], 3)

    # headline: drain throughput of the warmed replica over the family
    s0 = warmed.stats()
    t0 = time.perf_counter()
    for p in prompts:
        warmed.submit(p, max_new=max_new)
    warmed.run_until_drained()
    drain_s = time.perf_counter() - t0
    s1 = warmed.stats()
    gen = s1["tokens_generated"] - s0["tokens_generated"]
    out["warmed"].update(
        tokens_per_s=round(gen / drain_s, 1),
        drain_s=round(drain_s, 3), tokens_generated=gen)
    out["warmed"].update(
        {k: v for k, v in warmed.prefix_cache_stats().items()
         if k.startswith("prefix_")})
    out["ring_bytes_fetched"] = ring.bytes_got
    return out


def lm_paged_grid(platform: str) -> list[tuple[int, int]]:
    """(slots, context) points for BENCH_SUITE=lm_paged. TPU measures the
    serving-relevant 16/32 slots x 1k/4k contexts; CPU proves the
    machinery on a miniature. BENCH_LM_PAGED_GRID=s:c,s:c overrides."""
    env = os.environ.get("BENCH_LM_PAGED_GRID")
    if env:
        return [(int(s), int(c)) for s, c in
                (p.split(":") for p in env.split(",") if p.strip())]
    if platform == "tpu":
        return [(16, 1024), (32, 1024), (16, 4096), (32, 4096)]
    return [(2, 32), (2, 64)]


def run_lm_paged_bench(platform: str, device_kind: str, n_devices: int,
                       peak_bf16: float | None, *, deadline: float,
                       compact: bool = False) -> dict:
    """BENCH_SUITE=lm_paged: steady-state decode through radix hits
    consumed IN PLACE via the block table (`ops/paged_attention.py`) vs
    gathered into contiguous rows at admission — the paged path's
    serving-level evidence (ISSUE 7). Every slot serves the SAME full-
    context prompt (one shared chain, the shared-prefix regime the radix
    cache exists for), so admission is a full-depth hit and the timed
    dispatches are pure decode. Per grid point: ``paged`` (auto kernel =
    the shipped default) first — a deadline hit must cost the baseline —
    then ``gathered``, then ``paged_int8`` (the int8-native pool, ISSUE
    16: half the block-pool HBM traffic, scales dequantized in-path),
    then ``paged_pallas``/``paged_int8_pallas`` (the AUTO_KERNEL flip
    candidates; kernel-level grid lives in tools/flash_sweep.py)."""
    from idunno_tpu.engine.serve_lm import DecodeServer
    from idunno_tpu.models.transformer import TransformerLM

    cfg = lm_bench_config(platform)
    tpu = platform == "tpu"
    block = _env_int("BENCH_LM_KV_BLOCK", 16 if tpu else 4)
    out: dict = {"config": {k: v for k, v in cfg.items()},
                 "platform": platform, "device_kind": device_kind,
                 "n_devices": n_devices, "kv_block_size": block}
    dt = jnp.bfloat16
    model = TransformerLM(vocab=cfg["vocab"], dim=cfg["dim"],
                          depth=cfg["depth"], num_heads=cfg["heads"],
                          causal=True, dtype=dt, param_dtype=dt)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    n_params, _ = _count_params(params)
    out["n_params"] = n_params
    max_new = cfg["decode_steps"] * 3 + 1
    # int8 twin: same params, quantized KV block pool (ISSUE 16 — both
    # paged backends dequantize the per-token scales in-path)
    model_i8 = TransformerLM(vocab=cfg["vocab"], dim=cfg["dim"],
                             depth=cfg["depth"], num_heads=cfg["heads"],
                             causal=True, dtype=dt, param_dtype=dt,
                             kv_cache_dtype="int8")

    def run_point(slots: int, ctx: int, paged_kernel, lm=model) -> dict:
        per_chain = -(-ctx // block)
        srv = DecodeServer(lm, params, slots=slots, prompt_len=ctx,
                           max_len=ctx + max_new + 1,
                           decode_steps=cfg["decode_steps"],
                           kv_block_size=block,
                           kv_cache_blocks=2 * per_chain + 4,
                           paged_kernel=paged_kernel)
        prompt = [int(t) for t in np.random.default_rng(5).integers(
            1, cfg["vocab"], size=ctx)]
        t0 = time.perf_counter()
        srv.submit(prompt, max_new=2)      # seed the tree (cold compile)
        srv.run_until_drained()
        c_s = time.perf_counter() - t0
        for _ in range(slots):             # full-depth hits, shared chain
            srv.submit(prompt, max_new=max_new)
        srv.step()                         # admissions + first dispatch
        k = max(1, (max_new - 1) // cfg["decode_steps"] - 1)
        t0 = time.perf_counter()
        for _ in range(k):
            srv.step()
        disp = (time.perf_counter() - t0) / k
        st = srv.stats()
        rec = {"tokens_per_s": round(
                   slots * cfg["decode_steps"] / disp, 1),
               "dispatch_s": round(disp, 4), "timed_dispatches": k,
               "seed_s": round(c_s, 2),
               "prefill_tokens": st["prefill_tokens"],
               "kv_gather_bytes_saved": st["kv_gather_bytes_saved"],
               "prefix_hits": st["prefix_cache"]["hits"]}
        if peak_bf16:
            rec["mfu"] = round(rec["tokens_per_s"] * 2.0 * n_params
                               / peak_bf16, 4)
        del srv
        return rec

    points: list[dict] = []
    out["points"] = points
    modes = [("paged", "auto", model), ("gathered", None, model),
             ("paged_int8", "auto", model_i8)]
    if tpu or os.environ.get("BENCH_LM_PAGED_PALLAS") == "1":
        modes.append(("paged_pallas", "pallas", model))
        modes.append(("paged_int8_pallas", "pallas", model_i8))
    for slots, ctx in lm_paged_grid(platform):
        point: dict = {"slots": slots, "context": ctx}
        points.append(point)
        for name, kern, lm in modes:
            if points[:-1] and time.perf_counter() > deadline:
                point[name] = {"skipped": "time budget"}
                continue
            try:
                point[name] = run_point(slots, ctx, kern, lm)
            except Exception as e:  # noqa: BLE001 - record, never hide
                point[name] = {"error": f"{type(e).__name__}: {e}"}
        if "tokens_per_s" in point.get("paged", {}) and \
                "tokens_per_s" in point.get("gathered", {}):
            point["paged_vs_gathered"] = round(
                point["paged"]["tokens_per_s"]
                / point["gathered"]["tokens_per_s"], 3)
        if "tokens_per_s" in point.get("paged_int8", {}) and \
                "tokens_per_s" in point.get("paged", {}):
            point["int8_vs_native"] = round(
                point["paged_int8"]["tokens_per_s"]
                / point["paged"]["tokens_per_s"], 3)
    ok = [p for p in points if "tokens_per_s" in p.get("paged", {})]
    if ok:
        best = max(ok, key=lambda p: p["paged"]["tokens_per_s"])
        # headline of the `BENCH_SUITE=lm_paged` record (bench.py reads
        # out[value_key]["tokens_per_s"])
        out["best"] = {"slots": best["slots"], "context": best["context"],
                       "tokens_per_s": best["paged"]["tokens_per_s"]}
    return out


def lm_tp_grid(platform: str) -> list[tuple[int, int]]:
    """(n_model, slots) points for BENCH_SUITE=lm_tp. TPU measures the
    serving-relevant 16/32 slots at n_model 1 vs 2 (the two-chip split);
    CPU proves the machinery on a miniature.
    BENCH_LM_TP_GRID=m:s,m:s overrides."""
    env = os.environ.get("BENCH_LM_TP_GRID")
    if env:
        return [(int(m), int(s)) for m, s in
                (p.split(":") for p in env.split(",") if p.strip())]
    if platform == "tpu":
        return [(1, 16), (2, 16), (1, 32), (2, 32)]
    return [(1, 2), (2, 2), (1, 4), (2, 4)]


def run_lm_tp_bench(platform: str, device_kind: str, n_devices: int,
                    peak_bf16: float | None, *, deadline: float,
                    compact: bool = False) -> dict:
    """BENCH_SUITE=lm_tp: steady-state decode throughput of the tensor-
    parallel scanned pool (`parallel/sharding.py:lm_tp_specs` — Megatron
    column/row split, two psums per block inside the ONE lax.scan) at
    n_model 1 vs 2 (ISSUE 9). Each point times pure decode dispatches on
    a pure-TP mesh; paired points report the TP speedup AND a token-
    exactness probe (the first completion must match across n_model — the
    structural-exactness claim, checked on-chip). A point whose n_model
    exceeds the visible device count records a skip, not an error, so a
    single-chip window still captures the n_model=1 baseline."""
    from idunno_tpu.engine.serve_lm import DecodeServer
    from idunno_tpu.models.transformer import TransformerLM

    cfg = lm_bench_config(platform)
    out: dict = {"config": {k: v for k, v in cfg.items()},
                 "platform": platform, "device_kind": device_kind,
                 "n_devices": n_devices}
    dt = jnp.bfloat16
    model = TransformerLM(vocab=cfg["vocab"], dim=cfg["dim"],
                          depth=cfg["depth"], num_heads=cfg["heads"],
                          causal=True, dtype=dt, param_dtype=dt)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    n_params, _ = _count_params(params)
    out["n_params"] = n_params
    max_new = cfg["decode_steps"] * 3 + 1
    prompt_len = min(cfg["prompt_len"], 64)
    prompt = [int(t) for t in np.random.default_rng(5).integers(
        1, cfg["vocab"], size=prompt_len)]

    def run_point(n_model: int, slots: int) -> dict:
        srv = DecodeServer(model, params, slots=slots,
                           prompt_len=prompt_len,
                           max_len=prompt_len + max_new + 1,
                           decode_steps=cfg["decode_steps"],
                           n_model=n_model)
        t0 = time.perf_counter()
        srv.submit(prompt, max_new=2)          # cold compile
        head = srv.run_until_drained()[0].tokens
        c_s = time.perf_counter() - t0
        for _ in range(slots):
            srv.submit(prompt, max_new=max_new)
        srv.step()                             # admissions + first dispatch
        k = max(1, (max_new - 1) // cfg["decode_steps"] - 1)
        t0 = time.perf_counter()
        for _ in range(k):
            srv.step()
        disp = (time.perf_counter() - t0) / k
        st = srv.stats()["config"]
        rec = {"tokens_per_s": round(
                   slots * cfg["decode_steps"] / disp, 1),
               "dispatch_s": round(disp, 4), "timed_dispatches": k,
               "compile_s": round(c_s, 2),
               "tp_collective_bytes": st["tp_collective_bytes"],
               "head_tokens": head}
        if peak_bf16:
            rec["mfu"] = round(rec["tokens_per_s"] * 2.0 * n_params
                               / (peak_bf16 / max(1, n_model)), 4)
        del srv
        return rec

    points: list[dict] = []
    out["points"] = points
    base_heads: dict[int, list] = {}           # slots -> n_model=1 stream
    for n_model, slots in lm_tp_grid(platform):
        point: dict = {"n_model": n_model, "slots": slots}
        points.append(point)
        if n_model > n_devices:
            point["skipped"] = f"needs {n_model} devices, have {n_devices}"
            continue
        if points[:-1] and time.perf_counter() > deadline:
            point["skipped"] = "time budget"
            continue
        try:
            rec = run_point(n_model, slots)
        except Exception as e:  # noqa: BLE001 - record, never hide
            point["error"] = f"{type(e).__name__}: {e}"
            continue
        head = rec.pop("head_tokens")
        point.update(rec)
        if n_model == 1:
            base_heads[slots] = head
        elif slots in base_heads:
            # the structural-exactness claim, measured where it runs
            point["token_exact_vs_1"] = head == base_heads[slots]
            base = next((p for p in points
                         if p["n_model"] == 1 and p["slots"] == slots
                         and "tokens_per_s" in p), None)
            if base is not None:
                point["speedup_vs_1"] = round(
                    point["tokens_per_s"] / base["tokens_per_s"], 3)
    ok = [p for p in points if "tokens_per_s" in p]
    if ok:
        tp = [p for p in ok if p["n_model"] > 1] or ok
        best = max(tp, key=lambda p: p["tokens_per_s"])
        # headline of the `BENCH_SUITE=lm_tp` record (bench.py reads
        # out[value_key]["tokens_per_s"])
        out["best"] = {"n_model": best["n_model"], "slots": best["slots"],
                       "tokens_per_s": best["tokens_per_s"]}
    return out


def run_lm_gateway_bench(platform: str, device_kind: str, n_devices: int,
                         peak_bf16: float | None, *, deadline: float,
                         compact: bool = False) -> dict:
    """BENCH_SUITE=lm_gateway: goodput vs offered load through the QoS
    admission gateway (`serve/gateway.py` + `serve/admission.py`).

    Three phases on the SAME pool config: ``capacity`` (closed-loop drain,
    no gateway — the pool's intrinsic request rate, which sizes the
    offered loads), ``overload`` (open-loop Poisson arrivals at 2x
    capacity through the gateway — the headline record: goodput
    tokens/sec of admitted completions plus shed rate,
    `BENCH_SUITE=lm_gateway`), and ``underload`` (0.5x — the no-pressure
    control: shed rate should be ~0 and goodput ~the offered tokens).
    Mixed tenants/priorities come from `tools/gateway_load.py`'s default
    mix; batch's tighter backpressure slack makes it shed first, which is
    the class-protection behavior the record demonstrates."""
    import random as _random

    from idunno_tpu.engine.serve_lm import DecodeServer
    from idunno_tpu.models.transformer import TransformerLM
    from idunno_tpu.serve.gateway import AdmissionGateway
    from idunno_tpu.serve.lm_pool import LMServingLoop

    try:
        from tools.gateway_load import poisson_schedule, run_open_loop
    except ImportError:  # bench invoked from outside the repo root
        import sys
        repo = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        if repo not in sys.path:
            sys.path.insert(0, repo)
        from tools.gateway_load import poisson_schedule, run_open_loop

    cfg = lm_bench_config(platform)
    tpu = platform == "tpu"
    n_requests = _env_int("BENCH_LM_GW_REQUESTS", 64 if tpu else 32)
    out: dict = {"config": {k: v for k, v in cfg.items()},
                 "platform": platform, "device_kind": device_kind,
                 "n_devices": n_devices, "n_requests": n_requests}
    dt = jnp.bfloat16
    model = TransformerLM(vocab=cfg["vocab"], dim=cfg["dim"],
                          depth=cfg["depth"], num_heads=cfg["heads"],
                          causal=True, dtype=dt, param_dtype=dt)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    n_params, _ = _count_params(params)
    out["n_params"] = n_params

    max_new = min(cfg["decode_steps"] + 1,
                  cfg["max_len"] - cfg["prompt_len"])
    rng = np.random.default_rng(11)

    def prompt() -> list[int]:
        return [int(t) for t in
                rng.integers(1, cfg["vocab"], size=cfg["prompt_len"])]

    def make_server() -> DecodeServer:
        srv = DecodeServer(model, params, slots=cfg["slots"],
                           prompt_len=cfg["prompt_len"],
                           max_len=cfg["max_len"],
                           decode_steps=cfg["decode_steps"])
        srv.warmup()
        return srv

    # -- capacity: closed-loop drain, no gateway --------------------------
    srv = make_server()
    n_cap = 3 * cfg["slots"]
    t0 = time.perf_counter()
    for _ in range(n_cap):
        srv.submit(prompt(), max_new=max_new)
    srv.run_until_drained()
    cap_s = time.perf_counter() - t0
    s = srv.stats()
    capacity_rps = n_cap / cap_s
    out["capacity"] = {"requests": n_cap, "drain_s": round(cap_s, 3),
                       "requests_per_s": round(capacity_rps, 2),
                       "tokens_per_s": round(
                           s["tokens_generated"] / cap_s, 1)}

    # batch's tighter slack sheds bulk traffic first; slacks are tightened
    # below the serving defaults (2.0/4.0) so a bench-sized burst actually
    # crosses the thresholds — at the defaults the pipeline absorbs
    # n_requests at 2x without pressure and the record shows nothing
    gw_spec = {"max_queue": 4 * cfg["slots"],
               "batch_wait_slack": 1.0, "interactive_wait_slack": 3.0,
               "tenants": {"ivy": {"weight": 2.0},
                           "bulk": {"weight": 1.0}}}

    def open_loop_phase(multiple: float, seed: int) -> dict:
        loop = LMServingLoop(make_server(), name="gw-bench",
                             gateway=AdmissionGateway(gw_spec))
        try:
            sched = poisson_schedule(capacity_rps * multiple, n_requests,
                                     _random.Random(seed))
            budget = max(10.0, deadline - time.perf_counter())
            rec = run_open_loop(loop, sched, prompt_fn=prompt,
                                max_new=max_new,
                                drain_timeout_s=min(120.0, budget))
        finally:
            loop.stop()
        rec["load_multiple"] = multiple
        return rec

    # headline first: a deadline hit must cost the underload control, not
    # the overload record the suite exists to capture
    out["overload"] = open_loop_phase(2.0, seed=1)
    if time.perf_counter() < deadline:
        out["underload"] = open_loop_phase(0.5, seed=2)
    if peak_bf16:
        out["overload"]["mfu"] = round(
            out["overload"]["tokens_per_s"] * 2.0 * n_params / peak_bf16, 4)
    return out


def run_lm_autoscale_bench(platform: str, device_kind: str,
                           n_devices: int, peak_bf16: float | None, *,
                           deadline: float, compact: bool = False) -> dict:
    """BENCH_SUITE=lm_autoscale: what a replica spawn buys under SLO
    breach (`serve/autoscaler.py` + replica pool groups).

    `tools/autoscale_load.py` offers ramp (0.8x measured capacity) /
    overload (2x) / underload (0.3x) Poisson regimes to one
    gateway-fronted replica, then re-runs the overload regime against
    TWO replicas behind the group's round-robin decode routing — the
    headline (``overload_scaled``: goodput tokens/sec in the scaled-out
    configuration, `BENCH_SUITE=lm_autoscale`) against the 1-replica
    breach record. The measured per-regime interactive queue-wait p95s
    then drive a REAL `Autoscaler` tick-by-tick (manager stubbed), so
    ``autoscale.decisions`` shows the closed loop spawning at overload
    and draining/retiring at underload on this exact hardware."""
    from idunno_tpu.engine.serve_lm import DecodeServer
    from idunno_tpu.models.transformer import TransformerLM
    from idunno_tpu.serve.gateway import AdmissionGateway
    from idunno_tpu.serve.lm_pool import LMServingLoop

    try:
        from tools.autoscale_load import run_phases, summarize
    except ImportError:  # bench invoked from outside the repo root
        import sys
        repo = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        if repo not in sys.path:
            sys.path.insert(0, repo)
        from tools.autoscale_load import run_phases, summarize

    cfg = lm_bench_config(platform)
    tpu = platform == "tpu"
    n_requests = _env_int("BENCH_LM_AS_REQUESTS", 48 if tpu else 24)
    out: dict = {"config": {k: v for k, v in cfg.items()},
                 "platform": platform, "device_kind": device_kind,
                 "n_devices": n_devices, "n_requests": n_requests}
    dt = jnp.bfloat16
    model = TransformerLM(vocab=cfg["vocab"], dim=cfg["dim"],
                          depth=cfg["depth"], num_heads=cfg["heads"],
                          causal=True, dtype=dt, param_dtype=dt)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    n_params, _ = _count_params(params)
    out["n_params"] = n_params

    max_new = min(cfg["decode_steps"] + 1,
                  cfg["max_len"] - cfg["prompt_len"])
    rng = np.random.default_rng(13)

    def prompt() -> list[int]:
        return [int(t) for t in
                rng.integers(1, cfg["vocab"], size=cfg["prompt_len"])]

    # every group replica fronts its own gateway — same tightened slacks
    # as the gateway suite so bench-sized bursts register as queue wait
    gw_spec = {"max_queue": 4 * cfg["slots"],
               "batch_wait_slack": 1.0, "interactive_wait_slack": 3.0}

    def make_loop() -> LMServingLoop:
        srv = DecodeServer(model, params, slots=cfg["slots"],
                           prompt_len=cfg["prompt_len"],
                           max_len=cfg["max_len"],
                           decode_steps=cfg["decode_steps"])
        srv.warmup()
        return LMServingLoop(srv, name="autoscale-bench",
                             gateway=AdmissionGateway(dict(gw_spec)))

    # -- capacity: closed-loop drain on one replica sizes the offers ------
    srv = DecodeServer(model, params, slots=cfg["slots"],
                       prompt_len=cfg["prompt_len"], max_len=cfg["max_len"],
                       decode_steps=cfg["decode_steps"])
    srv.warmup()
    n_cap = 3 * cfg["slots"]
    t0 = time.perf_counter()
    for _ in range(n_cap):
        srv.submit(prompt(), max_new=max_new)
    srv.run_until_drained()
    cap_s = time.perf_counter() - t0
    capacity_rps = n_cap / cap_s
    out["capacity"] = {"requests": n_cap, "drain_s": round(cap_s, 3),
                       "requests_per_s": round(capacity_rps, 2)}

    phases = run_phases(make_loop, capacity_rps, n_requests=n_requests,
                        prompt_fn=prompt, max_new=max_new, seed=13,
                        deadline=deadline)
    out.update(phases)
    out["autoscale"] = summarize(phases)
    scaled = out.get("overload_scaled")
    if peak_bf16 and scaled and scaled.get("tokens_per_s"):
        scaled["mfu"] = round(
            scaled["tokens_per_s"] * 2.0 * n_params / peak_bf16, 4)
    return out


def _pct_ms(samples: list[float], q: float) -> float:
    """Percentile of per-token gap samples, in milliseconds."""
    if not samples:
        return 0.0
    return round(float(np.percentile(np.asarray(samples), q)) * 1000, 3)


def predictive_scale_ahead_record() -> dict:
    """Deterministic forecast demonstration for the distserve record: a
    scripted Poisson-burst arrival script (integer admissions per 1 s
    tick — a low-rate warm phase, then a ramp past capacity) driven
    through the REAL Holt filter (`serve/autoscaler.py:_forecast_update`)
    against one replica of capacity 1 rps. The record compares the
    predictive trigger tick (forecast at the horizon crosses capacity)
    with a reactive proxy — the first tick whose accumulated backlog
    implies a queue wait over the 1 s slack, i.e. the earliest a
    breach-driven scaler could fire. The trend term crosses during the
    ramp, while arrivals still fit capacity and the queue is empty, so
    the lead is structural, not tuned. The closed-loop version (real
    ``tick()`` spawning on a fake clock) lives in
    tests/test_autoscaler.py; this section just pins the filter's lead
    on the exact shipped constants."""
    from idunno_tpu.serve.autoscaler import AutoscalePolicy, Autoscaler
    pol = AutoscalePolicy(predict_horizon_s=6.0,
                          predict_capacity_rps=1.0)   # shipped a/b
    asc = Autoscaler(None, clock=lambda: 0.0)
    arrivals = [0, 1, 0, 0, 1, 0, 0, 1, 0,        # ~0.33 rps warm phase
                1, 0, 1, 1, 0, 1, 1, 1, 1,        # ramp toward capacity
                2, 1, 2, 2, 2, 3, 3, 3]           # burst past capacity
    cum, backlog = 0, 0.0
    trig_pred, trig_react = None, None
    series = []
    for t, a in enumerate(arrivals):
        cum += a
        gauges = {"r0": {"admitted": {"interactive": cum}, "n": 1}}
        pred = asc._forecast_update("g", pol, gauges, float(t))
        series.append(round(pred, 3))
        if trig_pred is None and pred > pol.predict_capacity_rps:
            trig_pred = t
        backlog = max(0.0, backlog + a - pol.predict_capacity_rps)
        if trig_react is None \
                and backlog / pol.predict_capacity_rps > 1.0:
            trig_react = t
    return {"arrivals_per_tick": arrivals,
            "capacity_rps": pol.predict_capacity_rps,
            "horizon_s": pol.predict_horizon_s,
            "alpha": pol.predict_alpha, "beta": pol.predict_beta,
            "predicted_series": series,
            "trigger_tick_predictive": trig_pred,
            "trigger_tick_reactive": trig_react,
            "lead_ticks": (trig_react - trig_pred
                           if trig_pred is not None
                           and trig_react is not None else None)}


def run_lm_distserve_bench(platform: str, device_kind: str,
                           n_devices: int, peak_bf16: float | None, *,
                           deadline: float, compact: bool = False) -> dict:
    """BENCH_SUITE=lm_distserve: what shipping prefilled KV blocks off
    the decode path buys (ISSUE 18 — DistServe-style disaggregation).

    One scripted workload, three serving arms: background short
    requests hold the decode slots at constant occupancy (closed loop —
    a finished short is resubmitted) while long prompts arrive every
    ``inject_every`` driver ticks. Per tick, every server with work
    runs one ``step()`` and its wall time is sampled whenever a LONG
    row was already decoding — the longs are the streams whose decode
    host differs between arms, and the per-token gap they observed (the
    inter-token latency) includes any prefill admission the step also
    ran. Arms:

    ``colocated``     one server takes everything; long full-bucket
                      prefills land inside the decode loop (worst ITL).
    ``role_split``    whole-request role routing (the pre-ISSUE-18
                      manager behavior): longs prefill AND decode on a
                      prefill server — its earlier longs' decode is
                      interrupted by each new long's prefill.
    ``handoff``       true DistServe: the prefill server fills + ships
                      the block chain (`handoff_export`), the decode
                      server grafts it (`handoff_adopt`) and admits
                      through a radix hit — only the sub-block suffix
                      prefills on the decode path (headline).

    Per-server sampling is the point: each arm's ITL distribution is
    what that arm's DECODING rows actually waited, so the single-process
    driver faithfully stands in for the two-host deployment (where the
    prefill host's work genuinely overlaps the decode host's loop; here
    the export simply happens between decode steps and is charged to the
    long request's TTFT, not to the decode rows). Headline is the
    handoff arm's throughput; ``decode_interference`` carries the p95
    comparison, ``predictive`` the scale-ahead forecast lead."""
    from idunno_tpu.engine.serve_lm import DecodeServer
    from idunno_tpu.models.transformer import TransformerLM

    cfg = lm_bench_config(platform)
    tpu = platform == "tpu"
    block = _env_int("BENCH_LM_KV_BLOCK", 16 if tpu else 4)
    short_len = cfg["prompt_len"]
    # the CPU miniature's prefill is dispatch-dominated, so the long
    # bucket must be MUCH wider than the suffix bucket for the
    # full-vs-suffix prefill cost difference to rise above the fixed
    # dispatch overhead; the TPU config's 4x gap is real compute
    long_len = _env_int("BENCH_LM_DS_LONG",
                        4 * short_len if tpu else 12 * short_len)
    n_long = _env_int("BENCH_LM_DS_LONGS", 8)
    # every tick, with each long decoding for ~4 ticks: longs OVERLAP on
    # whatever server decodes them, so a new long's prefill actually
    # interrupts an earlier long's decode — the interference under test
    inject_every = _env_int("BENCH_LM_DS_INJECT_EVERY", 1)
    max_new_long = (4 * cfg["decode_steps"] if not tpu else
                    min(2 * cfg["decode_steps"],
                        cfg["max_len"] - long_len))
    ds_max_len = max(cfg["max_len"], long_len + max_new_long)
    max_new_short = min(6 * cfg["decode_steps"],
                        ds_max_len - short_len)
    n_bg = max(1, cfg["slots"] - 1)
    buckets = (short_len, long_len)
    per_long = -(-long_len // block)
    pool_kw = dict(slots=cfg["slots"], prompt_len=long_len,
                   max_len=ds_max_len,
                   decode_steps=cfg["decode_steps"],
                   prompt_buckets=buckets, kv_block_size=block,
                   kv_cache_blocks=(n_long + 6) * per_long)
    out: dict = {"config": {k: v for k, v in cfg.items()},
                 "platform": platform, "device_kind": device_kind,
                 "n_devices": n_devices,
                 "workload": {"short_len": short_len,
                              "long_len": long_len,
                              "n_long": n_long, "bg_slots": n_bg,
                              "inject_every_ticks": inject_every,
                              "max_new_long": max_new_long,
                              "max_new_short": max_new_short,
                              "kv_block_size": block}}
    dt_ = jnp.bfloat16
    model = TransformerLM(vocab=cfg["vocab"], dim=cfg["dim"],
                          depth=cfg["depth"], num_heads=cfg["heads"],
                          causal=True, dtype=dt_, param_dtype=dt_)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    n_params, _ = _count_params(params)
    out["n_params"] = n_params

    rng0 = np.random.default_rng(17)
    longs = [[int(t) for t in
              rng0.integers(1, cfg["vocab"], size=long_len)]
             for _ in range(n_long)]
    warm_long = [int(t) for t in
                 rng0.integers(1, cfg["vocab"], size=long_len)]

    def run_arm(mode: str) -> dict:
        rng = np.random.default_rng(23)    # identical short stream/arm

        def short() -> list[int]:
            return [int(t) for t in
                    rng.integers(1, cfg["vocab"], size=short_len)]

        dec = DecodeServer(model, params, **pool_kw)
        dec.warmup()
        pre = None
        if mode != "colocated":
            pre = DecodeServer(model, params, **pool_kw)
            pre.warmup()
        # pay the long-bucket (and handoff graft / suffix-hit) compiles
        # outside the timed window, on a disjoint same-length prompt
        if mode == "colocated":
            dec.submit(warm_long, max_new=2)
            dec.run_until_drained()
        elif mode == "role_split":
            pre.submit(warm_long, max_new=2)
            pre.run_until_drained()
        else:
            d0 = dec.handoff_probe(warm_long)["depth"]
            exp = pre.handoff_export(warm_long, from_depth=d0)
            dec.handoff_adopt(warm_long, exp["blobs"], start_depth=d0)
            dec.submit(warm_long, max_new=2)
            dec.run_until_drained()

        servers = {"decode": dec}
        if pre is not None:
            servers["prefill"] = pre
        base = {k: s.stats() for k, s in servers.items()}
        # stagger the background shorts' lengths so they retire one at a
        # time — lockstep retirement frees slots in bulk and makes the
        # decode server admit several queued longs in ONE step, a burst
        # artifact no steady-state deployment would show
        n_short = 0

        def bg_max_new() -> int:
            nonlocal n_short
            n_short += 1
            return max(2 * cfg["decode_steps"],
                       max_new_short
                       - (n_short % 3) * cfg["decode_steps"])

        for _ in range(n_bg):
            dec.submit(short(), max_new=bg_max_new())

        long_host = pre if mode == "role_split" else dec
        rids: dict[int, int] = {}          # long index -> rid
        t_arrive: dict[int, float] = {}
        ttft: dict[int, float] = {}
        done: set[int] = set()
        samples = {k: [] for k in servers}
        prefill_steps = {k: 0 for k in servers}
        tick, next_long = 0, 0
        t_loop0 = time.perf_counter()
        while (len(done) < n_long or next_long < n_long) and tick < 400:
            if next_long < n_long and tick == inject_every * next_long:
                p = longs[next_long]
                t_arrive[next_long] = time.perf_counter()
                if mode == "handoff":
                    d0 = dec.handoff_probe(p)["depth"]
                    exp = pre.handoff_export(p, from_depth=d0)
                    dec.handoff_adopt(p, exp["blobs"], start_depth=d0)
                rids[next_long] = long_host.submit(
                    p, max_new=max_new_long)
                next_long += 1
            long_ids = set(rids.values())
            for k, srv in servers.items():
                if srv.pending() == 0:
                    continue
                # gate on a LONG row decoding: the longs are the streams
                # whose decode host differs between arms, so their
                # per-token gap is the interference comparison — shorts
                # stay on the decode server in every arm. Request ids
                # are per-server counters, so only the long host's rows
                # can be longs (a decode-server short can share a rid
                # number with a prefill-server long).
                long_live = srv is long_host and any(
                    r["id"] in long_ids for r in srv.snapshot())
                pf0 = srv.stats()["prefill_tokens"]
                t0 = time.perf_counter()
                srv.step()
                step_s = time.perf_counter() - t0
                if long_live:
                    samples[k].append(step_s / cfg["decode_steps"])
                    if srv.stats()["prefill_tokens"] > pf0:
                        prefill_steps[k] += 1
            now = time.perf_counter()
            snap = {r["id"]: r for r in long_host.snapshot()}
            long_rids = {rid: i for i, rid in rids.items()}
            for i, rid in rids.items():
                if i in ttft or i in done:
                    continue
                row = snap.get(rid)
                if row is not None \
                        and len(row["tokens"]) > row["prompt_len"]:
                    ttft[i] = now - t_arrive[i]
            for k, srv in servers.items():
                for comp in srv.poll():
                    i = long_rids.get(comp.id)
                    if srv is long_host and i is not None:
                        done.add(i)
                        ttft.setdefault(i, now - t_arrive[i])
                    elif srv is dec:
                        # finished background short: closed loop
                        dec.submit(short(), max_new=bg_max_new())
            tick += 1
        loop_s = time.perf_counter() - t_loop0
        gen = sum(s.stats()["tokens_generated"]
                  - base[k]["tokens_generated"]
                  for k, s in servers.items())
        allsamp = [x for v in samples.values() for x in v]
        arm = {"completed_longs": len(done), "ticks": tick,
               "wall_s": round(loop_s, 3),
               "tokens_generated": gen,
               "tokens_per_s": round(gen / loop_s, 1),
               "ttft_p50_s": (round(float(np.median(
                   list(ttft.values()))), 4) if ttft else None),
               "ttft_max_s": (round(max(ttft.values()), 4)
                              if ttft else None),
               "itl_p50_ms": _pct_ms(allsamp, 50),
               "itl_p95_ms": _pct_ms(allsamp, 95),
               "itl_samples": len(allsamp),
               "prefill_contaminated_steps": dict(prefill_steps)}
        if mode == "handoff":
            ps, ds = pre.stats(), dec.stats()
            arm["handoff_ships"] = (ps["kv_handoff_requests"]
                                    - base["prefill"]
                                    ["kv_handoff_requests"])
            arm["handoff_bytes"] = (ps["kv_handoff_bytes"]
                                    - base["prefill"]["kv_handoff_bytes"])
            arm["handoff_fallbacks"] = ds["kv_handoff_fallbacks"]
        return arm

    # headline first: a deadline hit must cost the comparison arms, not
    # the handoff record the capture step exists for
    out["handoff"] = run_arm("handoff")
    if time.perf_counter() < deadline:
        out["role_split"] = run_arm("role_split")
    if time.perf_counter() < deadline:
        out["colocated"] = run_arm("colocated")
    if "role_split" in out:
        h = out["handoff"]["itl_p95_ms"]
        r = out["role_split"]["itl_p95_ms"]
        out["decode_interference"] = {
            "handoff_itl_p95_ms": h,
            "role_split_itl_p95_ms": r,
            "colocated_itl_p95_ms": out.get("colocated", {})
                                       .get("itl_p95_ms"),
            "handoff_vs_role_split": round(h / r, 3) if r else None}
    out["predictive"] = predictive_scale_ahead_record()
    if peak_bf16 and out["handoff"].get("tokens_per_s"):
        out["handoff"]["mfu"] = round(
            out["handoff"]["tokens_per_s"] * 2.0 * n_params
            / peak_bf16, 4)
    return out


def _gray_hedged_poll(transport, hosts, cursor: int, *, delay_s: float,
                      merged: dict):
    """Tail-hedged ``lm_poll`` (contracts.HEDGE_SAFE): fire the primary
    ring host; if it has not answered within ``delay_s``, fire the backup
    and take the FIRST reply. The read is cursor-addressed — the same
    cursor returns the same row on either replica — so BOTH replies'
    rows land in ``merged`` keyed by cursor (the loser via ``on_late``)
    and duplicates collapse: delivery stays exactly-once no matter which
    replica answers first or how late the loser lands."""
    from idunno_tpu.comm.message import Message
    from idunno_tpu.comm.retry import call_hedged
    from idunno_tpu.utils.types import MessageType

    def fetch(host: str):
        def go():
            return transport.call(
                host, "control",
                Message(MessageType.INFERENCE, transport.host,
                        {"verb": "lm_poll", "cursor": cursor}))
        return go

    def merge(reply) -> None:
        if reply is not None and "row" in reply.payload:
            merged.setdefault(reply.payload["cursor"],
                              reply.payload["row"])

    out = call_hedged([fetch(h) for h in hosts], delay_s=delay_s,
                      on_late=merge)
    merge(out)
    return out


def run_lm_gray_bench(platform: str, device_kind: str, n_devices: int,
                      peak_bf16: float | None, *, deadline: float,
                      compact: bool = False) -> dict:
    """BENCH_SUITE=lm_gray: what the gray-failure defense buys a client
    whose replica limps without dying (ISSUE 20).

    Real decode work first: one `DecodeServer` drains a request batch
    and its completions become the rows two in-proc ring replicas serve
    (standby replication means either replica can answer ``lm_poll``).
    Replica r1 then limps — `InProcNetwork.slow_host` with a REAL
    ``sleep_s`` tail (bench mode; chaos schedules stay sleepless), so
    hedging has a real tail to cut, while the synthesized latency factor
    feeds the client's differential `HealthLedger`. Three polling arms
    over the identical cursor stream:

    ``baseline``    round-robin, no defense: every other poll eats the
                    full gray tail for the whole run.
    ``quarantine``  an attached ledger ticks per poll; once r1 is
                    QUARANTINED the client routes around it. The tail
                    vanishes after ``detect_poll`` — but every poll
                    before detection still ate it.
    ``hedged``      quarantine routing PLUS `_gray_hedged_poll` with a
                    hedge delay well under the tail: pre-detection polls
                    whose primary is the limping replica are answered by
                    the healthy backup at ~``hedge_ms`` instead of the
                    tail (headline; ``hedge_wins`` > 0 is the proof the
                    backup actually won, not just fired).

    Headline is the hedged arm's delivered-tokens/sec (client-observed:
    tokens in delivered rows over the arm's wall clock), so the gray
    tail directly costs the headline in the undefended arms. ``p99_cut``
    carries the client-observed p99 comparison."""
    from idunno_tpu.comm.inproc import InProcNetwork
    from idunno_tpu.comm.message import Message
    from idunno_tpu.comm.retry import reset_retry_counters, retry_counters
    from idunno_tpu.engine.serve_lm import DecodeServer
    from idunno_tpu.membership.health import HealthLedger, HealthPolicy
    from idunno_tpu.models.transformer import TransformerLM
    from idunno_tpu.utils.types import MessageType

    cfg = lm_bench_config(platform)
    tpu = platform == "tpu"
    n_requests = _env_int("BENCH_LM_GRAY_REQUESTS", 3 * cfg["slots"])
    n_polls = _env_int("BENCH_LM_GRAY_POLLS", 160 if tpu else 120)
    tail_s = _env_int("BENCH_LM_GRAY_TAIL_MS", 25) / 1000.0
    hedge_s = _env_int("BENCH_LM_GRAY_HEDGE_MS", 8) / 1000.0
    out: dict = {"config": {k: v for k, v in cfg.items()},
                 "platform": platform, "device_kind": device_kind,
                 "n_devices": n_devices,
                 "workload": {"n_requests": n_requests,
                              "n_polls": n_polls,
                              "tail_ms": round(tail_s * 1000, 1),
                              "hedge_ms": round(hedge_s * 1000, 1)}}
    dt = jnp.bfloat16
    model = TransformerLM(vocab=cfg["vocab"], dim=cfg["dim"],
                          depth=cfg["depth"], num_heads=cfg["heads"],
                          causal=True, dtype=dt, param_dtype=dt)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    n_params, _ = _count_params(params)
    out["n_params"] = n_params

    max_new = min(cfg["decode_steps"] + 1,
                  cfg["max_len"] - cfg["prompt_len"])
    rng = np.random.default_rng(29)
    srv = DecodeServer(model, params, slots=cfg["slots"],
                       prompt_len=cfg["prompt_len"],
                       max_len=cfg["max_len"],
                       decode_steps=cfg["decode_steps"])
    srv.warmup()
    t0 = time.perf_counter()
    for _ in range(n_requests):
        srv.submit([int(t) for t in
                    rng.integers(1, cfg["vocab"], size=cfg["prompt_len"])],
                   max_new=max_new)
    comps = srv.run_until_drained()
    drain_s = time.perf_counter() - t0
    gen = sum(len(c.tokens) - c.prompt_len for c in comps)
    out["decode"] = {"requests": len(comps), "drain_s": round(drain_s, 3),
                     "tokens_per_s": round(gen / drain_s, 1)}
    rows = [{"rid": c.id, "n_tokens": len(c.tokens) - c.prompt_len}
            for c in comps]

    net = InProcNetwork(seed=20)
    hosts = ("r0", "r1")
    client = net.transport("c0")
    for h in hosts:
        t = net.transport(h)

        def handle(service, msg, _h=h):
            cur = msg.payload["cursor"]
            return Message(MessageType.ACK, _h,
                           {"cursor": cur,
                            "row": dict(rows[cur % len(rows)], node=_h)})
        t.serve("control", handle)
    # factor feeds the ledger's synthesized latency; sleep_s is the REAL
    # tail the client's wall clock (and the hedge) actually sees
    net.slow_host("r1", 10.0, sleep_s=tail_s)
    # real-time detector sized to the bench: a handful of tail-length
    # polls must be enough to quarantine, or the arms measure detector
    # patience instead of routing
    pol = HealthPolicy(min_samples=4, suspect_window_s=2 * tail_s,
                       probation_s=8 * tail_s)

    def run_arm(mode: str) -> dict:
        ledger = None
        if mode != "baseline":
            ledger = HealthLedger("c0", policy=pol,
                                  clock=time.monotonic)
        client.health = ledger
        reset_retry_counters()
        merged: dict = {}
        lats: list[float] = []
        detect_poll = None
        t1 = time.perf_counter()
        for i in range(n_polls):
            order = [hosts[i % 2], hosts[(i + 1) % 2]]
            if ledger is not None:
                q = ledger.quarantined()
                order.sort(key=lambda h: h in q)   # healthy first, stable
            t2 = time.perf_counter()
            if mode == "hedged":
                _gray_hedged_poll(client, order, i, delay_s=hedge_s,
                                  merged=merged)
            else:
                reply = client.call(
                    order[0], "control",
                    Message(MessageType.INFERENCE, "c0",
                            {"verb": "lm_poll", "cursor": i}))
                merged.setdefault(reply.payload["cursor"],
                                  reply.payload["row"])
            lats.append(time.perf_counter() - t2)
            if ledger is not None:
                ledger.tick()
                if detect_poll is None and "r1" in ledger.quarantined():
                    detect_poll = i
        wall = time.perf_counter() - t1
        toks = sum(r["n_tokens"] for r in merged.values())
        arm = {"polls": n_polls, "wall_s": round(wall, 3),
               "rows_delivered": len(merged),
               "tokens_per_s": round(toks / wall, 1),
               "p50_ms": _pct_ms(lats, 50), "p95_ms": _pct_ms(lats, 95),
               "p99_ms": _pct_ms(lats, 99)}
        if ledger is not None:
            arm["detect_poll"] = detect_poll
            arm["health"] = ledger.gauges()
        if mode == "hedged":
            c = retry_counters()
            arm["hedged_rpcs"] = c["hedged_rpcs"]
            arm["hedge_wins"] = c["hedge_wins"]
        client.health = None
        return arm

    # headline first: a deadline hit must cost the comparison arms
    out["hedged"] = run_arm("hedged")
    if time.perf_counter() < deadline:
        out["baseline"] = run_arm("baseline")
    if time.perf_counter() < deadline:
        out["quarantine"] = run_arm("quarantine")
    net.clear_slow()
    if "baseline" in out:
        b, h = out["baseline"]["p99_ms"], out["hedged"]["p99_ms"]
        out["p99_cut"] = {
            "baseline_p99_ms": b, "hedged_p99_ms": h,
            "quarantine_p99_ms": out.get("quarantine", {}).get("p99_ms"),
            "hedged_vs_baseline": round(h / b, 3) if b else None}
    return out

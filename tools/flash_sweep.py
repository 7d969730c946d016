"""Flash-attention block-size sweep on the live chip (round-4 VERDICT
weak #2 / next-7: prefill flash measured 10.3% MFU with untuned 128x128
blocks and no captured XLA baseline — the kernel must EARN its default by
measurement, same discipline as the s2d stem).

Times the full LM-suite prefill forward (`utils/lm_bench.py` shapes,
scan-tiled dispatch) through:

  - stock XLA attention (the swap candidate),
  - the Pallas flash kernel at several (block_q, block_k) configs,

plus a decode-shaped paged-attention section (ISSUE 7): the block-native
kernel (`ops/paged_attention.py`) vs its XLA gather fallback at serving
shapes — q_len 1 and 8 (plain decode / fused spec verify) x KV 512 and
4096 x block sizes 16/32/64 — the evidence `AUTO_KERNEL` needs before it
may flip to "pallas" (earn-it-or-swap, same discipline as the prefill
default above), and a `paged_int8` section (ISSUE 16): the same two
kernels over int8 pages with per-token scale columns dequantized
in-path, at the quantized pool's decode shapes.

Writes FLASH_SWEEP.json incrementally after EVERY variant (a run that
is cut mid-sweep still leaves the variants it measured). Each variant is
one fresh compile (disk-cached across runs via the persistent compile
cache).

    python tools/flash_sweep.py           # real TPU
    python tools/flash_sweep.py --cpu     # machinery dry-run (interpret)
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# neighbors of the 2026-08-01 winner (256x1024) ride at the end so the
# budget clamp cuts them before the established grid: the default must
# sit in a measured local optimum, not at an unexplored grid edge
BLOCKS = [(128, 128), (256, 256), (512, 512), (128, 512), (256, 1024),
          (256, 512), (512, 1024), (512, 256)]
# second sequence length (VERDICT next-7: a default resting on one shape
# is a coincidence, not a tuning): the winner + its big-block neighbor +
# the XLA baseline again at 4x4096
LONGSEQ_BLOCKS = [(256, 1024), (512, 1024)]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--budget-s", type=float, default=float(
        os.environ.get("BENCH_TIME_BUDGET_S", "600")))
    ap.add_argument("--out", default=os.path.join(REPO, "FLASH_SWEEP.json"))
    args = ap.parse_args()

    import jax
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np

    from bench import peak_bf16_for, provenance
    from idunno_tpu.models.transformer import TransformerLM, make_attn_fn
    from idunno_tpu.ops.flash_attention import resolve_blocks
    from idunno_tpu.utils.compile_cache import enable_persistent_cache
    from idunno_tpu.utils.lm_bench import (lm_bench_config,
                                           prefill_flops_per_token,
                                           timed_prefill_dispatch)
    enable_persistent_cache()

    t_start = time.perf_counter()
    dev = jax.devices()[0]
    platform = dev.platform
    if not args.cpu and platform != "tpu":
        print(json.dumps({"error": f"need a TPU, got {platform}"}))
        return 2

    cfg = lm_bench_config(platform)
    dt = jnp.bfloat16 if platform == "tpu" else jnp.float32
    b, t, tile = cfg["prefill_batch"], cfg["prefill_seq"], max(
        1, cfg["prefill_tile"])
    base = dict(vocab=cfg["vocab"], dim=cfg["dim"], depth=cfg["depth"],
                num_heads=cfg["heads"], causal=True, dtype=dt,
                param_dtype=dt)
    model0 = TransformerLM(**base)
    params = model0.init(jax.random.PRNGKey(0),
                         jnp.zeros((1, 8), jnp.int32))["params"]
    n_params = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params))
    peak = peak_bf16_for(jax.devices()) if platform == "tpu" else None
    toks = jnp.asarray(np.random.default_rng(0).integers(
        1, cfg["vocab"], size=(tile, b, t)), jnp.int32)

    out: dict = {"platform": platform,
                 "device_kind": getattr(dev, "device_kind", platform),
                 "batch": b, "seq": t, "scan_tile": tile,
                 "model": {k: cfg[k] for k in
                           ("dim", "depth", "heads", "vocab")},
                 "variants": []}

    def flush(final: bool = False):
        """Incremental progress goes to <out>.partial.json; the REAL
        artifact is written only on a decision-grade sweep — xla
        baseline AND at least one flash variant measured — so a run cut
        after the baseline alone can't pass for a comparison."""
        out["provenance"] = provenance()
        if args.cpu:
            return
        path = args.out if final else args.out + ".partial.json"
        with open(path, "w") as f:
            json.dump(out, f, indent=1)
        if final:
            # the sidecar is progress insurance only — leaving it behind
            # ships a stale mid-sweep record next to the real artifact
            try:
                os.remove(args.out + ".partial.json")
            except OSError:
                pass

    def record(label, attn_kw, toks_arr=None, dest=None):
        toks_arr = toks if toks_arr is None else toks_arr
        dest = out["variants"] if dest is None else dest
        tl, bb, tt = toks_arr.shape
        try:
            attn = make_attn_fn(**attn_kw)
            m = TransformerLM(**base, attn_fn=attn)
            sec, c_s = timed_prefill_dispatch(m, params, toks_arr)
            row = {"variant": label,
                   "tokens_per_s": round(tl * bb * tt / sec, 1),
                   "median_s": round(sec, 4), "compile_s": round(c_s, 2)}
            if peak:
                flops_tok = prefill_flops_per_token(
                    n_params, tt, cfg["dim"], cfg["depth"])
                row["mfu"] = round(
                    (tl * bb * tt / sec) * flops_tok / peak, 4)
        except Exception as e:  # noqa: BLE001
            row = {"variant": label, "error": f"{type(e).__name__}: {e}"}
        dest.append(row)
        flush()
        print(json.dumps(row), flush=True)

    record("xla_full", {"kind": "full"})
    measured_geom: set = set()
    for bq, bk in BLOCKS:
        if time.perf_counter() - t_start > args.budget_s:
            out["variants"].append({"variant": f"flash_{bq}x{bk}",
                                    "skipped": "time budget"})
            flush()
            continue
        # label with the geometry that will actually execute: a request
        # the padded length cannot host is lowered by the kernel
        # (ops/flash_attention.py:resolve_blocks), never mislabeled here
        # — and two requests lowering to the same geometry are the same
        # measurement, not worth a second compile
        ebq, ebk, _ = resolve_blocks(t, bq, bk)
        if (ebq, ebk) in measured_geom:
            out["variants"].append(
                {"variant": f"flash_{bq}x{bk}",
                 "skipped": f"duplicate effective geometry {ebq}x{ebk}"})
            flush()
            continue
        measured_geom.add((ebq, ebk))
        kw = {"kind": "flash", "block_q": bq, "block_k": bk}
        if args.cpu:
            kw["interpret"] = True
        label = f"flash_{bq}x{bk}"
        if (ebq, ebk) != (bq, bk):
            label += f"_effective_{ebq}x{ebk}"
        record(label, kw)

    # -- second sequence length: 4x4096 (the default must hold on more
    # than the suite's native shape — long prompts are where flash's
    # O(seq) memory actually bites). Rides AFTER the main grid so a
    # short window still produces the decision-grade sweep above; the
    # xla baseline is re-measured at this shape so the comparison stays
    # per-shape honest.
    b_long = 4
    t_long = 4096 if platform == "tpu" else 2 * t
    toks_long = jnp.asarray(np.random.default_rng(1).integers(
        1, cfg["vocab"], size=(1, b_long, t_long)), jnp.int32)
    ls: list = []
    out["long_seq"] = {"batch": b_long, "seq": t_long, "scan_tile": 1,
                       "variants": ls}
    geom_long: set = set()
    for label, bq, bk in [("xla_full", None, None)] + [
            (f"flash_{bq}x{bk}", bq, bk) for bq, bk in LONGSEQ_BLOCKS]:
        if time.perf_counter() - t_start > args.budget_s:
            ls.append({"variant": label, "skipped": "time budget"})
            flush()
            continue
        if bq is None:
            record(label, {"kind": "full"}, toks_long, ls)
            continue
        ebq, ebk, _ = resolve_blocks(t_long, bq, bk)
        if (ebq, ebk) in geom_long:
            ls.append({"variant": label,
                       "skipped": f"duplicate effective geometry "
                                  f"{ebq}x{ebk}"})
            flush()
            continue
        geom_long.add((ebq, ebk))
        kw = {"kind": "flash", "block_q": bq, "block_k": bk}
        if args.cpu:
            kw["interpret"] = True
        if (ebq, ebk) != (bq, bk):
            label += f"_effective_{ebq}x{ebk}"
        record(label, kw, toks_long, ls)

    # -- decode-shaped paged attention: block-table addressing (pallas)
    # vs gather-then-attend (xla) at steady-serving shapes. Rides LAST:
    # each point is a tiny compile, but the prefill sweep above is the
    # older debt. 16 slots, MHA grouping (G=1) — the serving pool's
    # paged path calls this exact function per scanned layer.
    from idunno_tpu.ops.paged_attention import paged_attention_grouped
    kvh, hd = cfg["heads"], cfg["dim"] // cfg["heads"]
    slots = 16
    pv: list = []
    out["paged_decode"] = {"slots": slots, "kv_heads": kvh, "head_dim": hd,
                           "variants": pv}
    prng = np.random.default_rng(2)

    def time_paged(kernel, q, kp, vp, tables, lengths, scales=()):
        f = jax.jit(lambda q, kp, vp, tb, ln, *sc: paged_attention_grouped(
            q, kp, vp, tb, ln,
            **dict(zip(("k_scale_pages", "v_scale_pages"), sc)),
            kernel=kernel, interpret=args.cpu))
        operands = (q, kp, vp, tables, lengths, *scales)
        t0 = time.perf_counter()
        f(*operands)[0].block_until_ready()
        c_s = time.perf_counter() - t0
        reps = []
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(10):
                o, _ = f(*operands)
            o.block_until_ready()
            reps.append((time.perf_counter() - t0) / 10)
        return float(np.median(reps)), c_s

    for pbs in (32, 16, 64):              # likely winner first: budget
        for kv_len in (512, 4096):        # clamps cut the grid edge
            nb_row = kv_len // pbs
            kp = jnp.asarray(prng.standard_normal(
                (slots * nb_row, pbs, kvh, hd)), dt)
            vp = jnp.asarray(prng.standard_normal(
                (slots * nb_row, pbs, kvh, hd)), dt)
            tables = jnp.asarray(prng.permutation(slots * nb_row)
                                 .reshape(slots, nb_row), jnp.int32)
            lengths = jnp.full((slots,), kv_len, jnp.int32)
            kv_bytes = 2 * slots * kv_len * kvh * hd * np.dtype(
                np.float32 if dt == jnp.float32 else np.float16).itemsize
            for q_len in (1, 8):
                q = jnp.asarray(prng.standard_normal(
                    (slots, q_len, kvh, 1, hd)), dt)
                for kern in ("pallas", "xla"):
                    label = f"paged_{kern}_bs{pbs}_kv{kv_len}_q{q_len}"
                    if time.perf_counter() - t_start > args.budget_s:
                        pv.append({"variant": label,
                                   "skipped": "time budget"})
                        flush()
                        continue
                    try:
                        sec, c_s = time_paged(kern, q, kp, vp,
                                              tables, lengths)
                        row = {"variant": label,
                               "median_us": round(sec * 1e6, 1),
                               "kv_gb_per_s": round(kv_bytes / sec / 1e9,
                                                    2),
                               "compile_s": round(c_s, 2)}
                    except Exception as e:  # noqa: BLE001
                        row = {"variant": label,
                               "error": f"{type(e).__name__}: {e}"}
                    pv.append(row)
                    flush()
                    print(json.dumps(row), flush=True)

    # -- int8-native paged decode (ISSUE 16): the quantized pool's block
    # tiles ride the SAME kernels with per-token scale pages dequantized
    # in-path (pallas: in-VMEM right after the int8->f32 cast; xla:
    # after the gather). Decode shape only (q_len 1) — the int8 pool's
    # serving regime; the native grid above already maps the q_len axis.
    pi: list = []
    out["paged_int8"] = {"slots": slots, "kv_heads": kvh, "head_dim": hd,
                         "variants": pi}
    for pbs in (32, 16):
        for kv_len in (512, 4096):
            nb_row = kv_len // pbs
            kq = jnp.asarray(prng.integers(
                -127, 128, size=(slots * nb_row, pbs, kvh, hd)), jnp.int8)
            vq = jnp.asarray(prng.integers(
                -127, 128, size=(slots * nb_row, pbs, kvh, hd)), jnp.int8)
            ks = jnp.asarray(prng.uniform(
                0.5, 1.5, size=(slots * nb_row, pbs, kvh)), jnp.float32)
            vs = jnp.asarray(prng.uniform(
                0.5, 1.5, size=(slots * nb_row, pbs, kvh)), jnp.float32)
            tables = jnp.asarray(prng.permutation(slots * nb_row)
                                 .reshape(slots, nb_row), jnp.int32)
            lengths = jnp.full((slots,), kv_len, jnp.int32)
            # HBM the quantized pool actually moves: int8 K+V plus the
            # two f32 scale columns per (token, kv-head)
            kv_bytes = (2 * slots * kv_len * kvh * hd
                        + 2 * slots * kv_len * kvh * 4)
            q = jnp.asarray(prng.standard_normal(
                (slots, 1, kvh, 1, hd)), dt)
            for kern in ("pallas", "xla"):
                label = f"paged_{kern}_bs{pbs}_kv{kv_len}_q1"
                if time.perf_counter() - t_start > args.budget_s:
                    pi.append({"variant": label, "skipped": "time budget"})
                    flush()
                    continue
                try:
                    sec, c_s = time_paged(kern, q, kq, vq, tables,
                                          lengths, scales=(ks, vs))
                    row = {"variant": label,
                           "median_us": round(sec * 1e6, 1),
                           "kv_gb_per_s": round(kv_bytes / sec / 1e9, 2),
                           "compile_s": round(c_s, 2)}
                except Exception as e:  # noqa: BLE001
                    row = {"variant": label,
                           "error": f"{type(e).__name__}: {e}"}
                pi.append(row)
                flush()
                print(json.dumps(row), flush=True)

    # per-shape pallas-vs-xla verdict: AUTO_KERNEL may flip to "pallas"
    # only if the kernel wins at EVERY measured serving shape — a split
    # decision keeps the gather fallback (it is never wrong, only slow).
    # The int8 grid gets its own verdict line: its winner informs the
    # int8 pools' default independently of the native-dtype decision.
    def verdict(rows: list, dest: dict) -> None:
        pairs: dict = {}
        for v in rows:
            if "median_us" not in v:
                continue
            kern, shape = v["variant"].split("_", 2)[1], v["variant"].split(
                "_", 2)[2]
            pairs.setdefault(shape, {})[kern] = v["median_us"]
        both = {s: d for s, d in pairs.items() if len(d) == 2}
        if both:
            wins = sum(d["pallas"] < d["xla"] for d in both.values())
            dest["pallas_wins"] = f"{wins}/{len(both)}"
            dest["recommendation"] = (
                "flip ops/paged_attention.py:AUTO_KERNEL to 'pallas'"
                if wins == len(both) else
                "keep AUTO_KERNEL='xla' (gather fallback)")
        else:
            dest["incomplete"] = (
                "need pallas AND xla at >=1 shape for a default decision")

    verdict(pv, out["paged_decode"])
    verdict(pi, out["paged_int8"])

    ok = [v for v in out["variants"] if "tokens_per_s" in v]
    flash_ok = [v for v in ok if v["variant"].startswith("flash_")]
    xla_ok = [v for v in ok if v["variant"] == "xla_full"]
    # a recommendation needs BOTH sides of the comparison measured
    if flash_ok and xla_ok:
        best = max(ok, key=lambda v: v["tokens_per_s"])
        out["best"] = best["variant"]
        out["recommendation"] = (
            "swap prefill default to stock XLA attention"
            if best["variant"] == "xla_full"
            else f"keep flash; pin blocks via {best['variant']}")
        flush(final=True)
    else:
        out["incomplete"] = ("need xla_full AND >=1 flash variant "
                             "measured before a default decision")
        flush()
    print(json.dumps({k: out.get(k)
                      for k in ("best", "recommendation", "incomplete")}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

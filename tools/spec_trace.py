"""Trace-apportion ONE speculative decode dispatch vs ONE plain dispatch.

The 2026-08-01 lm_suite capture measured fused speculation at 0.41x plain
even at the constructed 100%-acceptance ceiling: ~30 ms per draft+verify
round against 2.5 ms per plain decode step at the same shapes, where the
model arithmetic (4 tiny-draft steps + one 5-token verify) predicts
~5-6 ms. The HLO copy census (tools/spec_copy_census.py) already ruled
out cache-sized copies — the spec program's cache-op profile is identical
to plain's. This tool gets the remaining answer the same way the decode
and preprocess fixes were found: capture a traced dispatch on the chip
and apportion device time per op.

The attribution trick is execution COUNT: inside one spec dispatch of R
rounds with draft length g, draft-loop ops run R*g times, verify/commit
ops run R times, so `device_op_times` counts split the round cost into
draft-loop vs verify/commit vs residual without any op-name guessing.

Three traced dispatches: plain decode, the speculative pool all-greedy
(the fast path introduced with `greedy_commit` — the constructed
ceiling), and the SAME compiled speculative program with sampled rows
live (the runtime cond takes the full sampling branch), so one window
apportions both branches and the greedy-vs-sampled delta IS the cost of
the machinery the fast path skips.

Writes SPEC_TRACE.json (+ raw .trace/lm_spec{,_plain,_sampled}).
Smoke-testable off-TPU: --cpu runs tiny
shapes with the same pool wiring but skips the profiler and artifact.
"""
from __future__ import annotations

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main() -> int:
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--out", default=os.path.join(REPO, "SPEC_TRACE.json"))
    args = ap.parse_args()

    import jax
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np

    from bench import provenance
    from idunno_tpu.engine.serve_lm import DecodeServer
    from idunno_tpu.models.transformer import TransformerLM
    from idunno_tpu.utils.compile_cache import enable_persistent_cache
    from idunno_tpu.utils.lm_bench import (lm_bench_config, spec_max_new,
                                           spec_rounds)
    from idunno_tpu.utils.tracing import trace
    enable_persistent_cache()

    dev = jax.devices()[0]
    platform = dev.platform
    if platform != "tpu" and not args.cpu:
        print(json.dumps({"error": f"need a TPU, got {platform}"}))
        return 2

    cfg = lm_bench_config(platform)
    dt = jnp.bfloat16 if platform == "tpu" else jnp.float32
    model = TransformerLM(vocab=cfg["vocab"], dim=cfg["dim"],
                          depth=cfg["depth"], num_heads=cfg["heads"],
                          causal=True, dtype=dt, param_dtype=dt)
    # zeroed trees = the bench's constructed 100%-acceptance pair: logits
    # agree everywhere, so every round commits the full chunk and the
    # traced dispatch is the mechanism ceiling, not a rejection study
    zt = jax.tree.map(jnp.zeros_like, model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    draft_model = TransformerLM(vocab=cfg["vocab"], dim=cfg["draft_dim"],
                                depth=cfg["draft_depth"],
                                num_heads=max(1, cfg["heads"] // 4),
                                causal=True, dtype=dt, param_dtype=dt)
    zd = jax.tree.map(jnp.zeros_like, draft_model.init(
        jax.random.PRNGKey(1), jnp.zeros((1, 8), jnp.int32))["params"])

    gamma, chunk = cfg["draft_len"], cfg["draft_len"] + 1
    n_rounds = spec_rounds(cfg)
    out: dict = {"platform": platform,
                 "device_kind": getattr(dev, "device_kind", platform),
                 "config": {k: cfg[k] for k in
                            ("dim", "depth", "heads", "vocab", "slots",
                             "prompt_len", "max_len", "decode_steps",
                             "draft_dim", "draft_depth", "draft_len")},
                 "rounds_per_dispatch": n_rounds}

    def traced_dispatch(srv, steps_label: str, temperature: float = 0.0):
        """Warm the pool, load every slot, run one compiled dispatch, then
        ONE more under the profiler; returns (trace_dir, wall_s).
        ``temperature`` > 0 loads SAMPLED rows, forcing the spec round's
        full sampling branch (the all-greedy fast path otherwise skips
        the draft-distribution/uniform machinery entirely)."""
        srv.submit([1, 2, 3], max_new=2)
        srv.run_until_drained()                      # compile
        for _ in range(cfg["slots"]):
            srv.submit(list(range(1, cfg["prompt_len"] + 1)),
                       max_new=spec_max_new(cfg),
                       temperature=temperature)
        srv.step()                                   # admission + warm step
        tdir = os.path.join(REPO, ".trace", steps_label)
        t0 = time.perf_counter()
        if args.cpu:
            srv.step()
            return None, time.perf_counter() - t0
        with trace(tdir):
            srv.step()
            np.asarray(srv._cursors)                 # force D2H sync
        return tdir, time.perf_counter() - t0

    plain = DecodeServer(model, zt, slots=cfg["slots"],
                         prompt_len=cfg["prompt_len"],
                         max_len=cfg["max_len"],
                         decode_steps=cfg["decode_steps"])
    pdir, p_wall = traced_dispatch(plain, "lm_spec_plain")
    del plain
    spec = DecodeServer(model, zt, slots=cfg["slots"],
                        prompt_len=cfg["prompt_len"],
                        max_len=cfg["max_len"],
                        draft=(draft_model, zd), draft_len=gamma,
                        decode_steps=n_rounds)
    sdir, s_wall = traced_dispatch(spec, "lm_spec")
    # same compiled program, sampled rows live → the runtime cond takes
    # the FULL sampling branch: one extra traced dispatch (seconds, no
    # recompile) apportions the machinery the greedy fast path skips
    ssdir, ss_wall = traced_dispatch(spec, "lm_spec_sampled",
                                     temperature=1.0)
    del spec

    out["plain"] = {"wall_s": round(p_wall, 4),
                    "steps": cfg["decode_steps"],
                    "wall_ms_per_step": round(1e3 * p_wall
                                              / cfg["decode_steps"], 3)}
    out["spec"] = {"wall_s": round(s_wall, 4), "rounds": n_rounds,
                   "wall_ms_per_round": round(1e3 * s_wall / n_rounds, 3)}
    out["spec_sampled"] = {
        "wall_s": round(ss_wall, 4), "rounds": n_rounds,
        "wall_ms_per_round": round(1e3 * ss_wall / n_rounds, 3)}

    if not args.cpu:
        from tools.parse_trace import apportion, device_op_times, \
            load_xspace

        def count_split(tdir):
            # count-based split of a spec dispatch: R*gamma-count ops are
            # the draft loop, R-count ops are verify+commit, everything
            # else is residual (entry staging, retirement, odd fusions).
            # gamma == 1 makes the two counts identical — the split can't
            # distinguish the lanes, so report them combined rather than
            # silently attributing everything to the draft loop
            ops, _ = device_op_times(load_xspace(tdir)[0])
            if gamma == 1:
                split = {"round_ops_ms": 0.0, "residual_ms": 0.0,
                         "note": "gamma=1: draft and verify execution "
                                 "counts coincide; lanes not separable"}
                for name, (sec, count) in ops.items():
                    key = ("round_ops_ms"
                           if count % n_rounds == 0 and count > 0
                           else "residual_ms")
                    split[key] += sec * 1e3
                return split
            split = {"draft_loop_ms": 0.0, "verify_commit_ms": 0.0,
                     "residual_ms": 0.0}
            for name, (sec, count) in ops.items():
                if count % (n_rounds * gamma) == 0 and count > 0:
                    split["draft_loop_ms"] += sec * 1e3
                elif count % n_rounds == 0 and count > 0:
                    split["verify_commit_ms"] += sec * 1e3
                else:
                    split["residual_ms"] += sec * 1e3
            return split

        out["plain"]["apportion"] = apportion(pdir,
                                              steps=cfg["decode_steps"])
        for key, tdir in (("spec", sdir), ("spec_sampled", ssdir)):
            out[key]["apportion"] = apportion(tdir, steps=n_rounds)
            split = count_split(tdir)
            out[key]["count_split"] = {
                k: round(v, 2) if isinstance(v, float) else v
                for k, v in split.items()}
            out[key]["count_split_per_round_ms"] = {
                k: round(v / n_rounds, 3)
                for k, v in split.items() if isinstance(v, float)}

    out["provenance"] = provenance()
    if not args.cpu:
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    print(json.dumps({k: out[k] for k in ("plain", "spec", "spec_sampled")
                      if k in out}, default=str)[:2000])
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

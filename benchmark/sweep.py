"""Several windows of one cell in one process, so that set-up is paid once:
the sweep that finds an open-loop cell's knee (the highest rate at which the
backlog at the window's end is no longer than at its start), and short
windows over many seeds for the limits of `correct`.

    python3 benchmark/sweep.py --workload <cell> --seconds 12 --rates 2,4,6,8
    python3 benchmark/sweep.py --workload <cell> --seconds 8 --seeds 1,2,3 --check fp8

It prints one JSON line a window and is no part of a measured run.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=20240901)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--rates", default="")
    ap.add_argument("--repeat", type=int, default=1)
    ap.add_argument("--serving", default="{}",
                    help="JSON merged into the configuration's serving block")
    ap.add_argument("--mix", default="{}", help="JSON merged into the mix")
    ap.add_argument("--check", default="",
                    help="'ref', or a control precision such as 'fp8'")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    from benchmark import check, harness, manifest, system, timing, traffic

    man = manifest.Manifest()
    cell = man.cell(args.workload)
    cfg = man.config(cell)
    family = man.family(cfg)
    cfg = system.model_config(cfg, args.rehearse, family)
    cfg["serving"] = dict(cfg["serving"], **json.loads(args.serving))
    mix = dict(man.mix(cell), **json.loads(args.mix))
    device = system.require_chips(cell["chips"], args.rehearse)
    if not args.rehearse:
        harness.cache_dir()
    t0 = timing.clock()
    ses = harness.Session(cfg, family, mix, args.seed, args.seconds,
                          trace_on=False, rehearse=args.rehearse,
                          device=device)
    print(json.dumps({"built_s": timing.clock() - t0, "device": device,
                      "peak_gb": system.memory_peak_bytes(1) / 1e9,
                      "bytes_limit": (jax.devices()[0].memory_stats() or {}
                                      ).get("bytes_limit"),
                      "slots": cfg["serving"]["slots"],
                      "kv_cache_blocks": cfg["serving"]["kv_cache_blocks"]}),
          flush=True)
    rates = [float(r) for r in args.rates.split(",") if r] or [None]
    n = 0
    for rate in rates:
        for _ in range(args.repeat):
            n += 1
            m = dict(mix) if rate is None else dict(mix, rate_rps=rate)
            ses.mix = m
            tr = traffic.generate(m, cfg["serving"], cfg["vocab_size"],
                                  args.seed + n, args.seconds,
                                  shrink=ses.shrink, prefix_seed=args.seed)
            run, gen, comps, _marks, _dir = ses.window(tr, args.seconds)
            attempted, failed = ses.records(run, gen, comps)
            e2e = timing.end_to_end(run.records, run.steps, run.w0, run.w1)
            late = [(r["sent"] - r["due"]) * 1e3 for r in run.records
                    if "sent" in r]
            steps = [s for s in run.steps if run.w0 <= s[1] < run.w1 and s[2]]
            backlog = [st["queued"] + st["inbox"]
                       for st in (run.stats0, run.stats1)]
            out = {"rate": rate, "seed": args.seed + n, "due": attempted,
                   "failed": failed, "backlog": backlog,
                   "live": [run.stats0["live"], run.stats1["live"]],
                   "step_p50_ms": timing.percentile(
                       [(s[1] - s[0]) * 1e3 for s in steps], 50),
                   "rows_mean": (sum(s[2] for s in steps) / len(steps)
                                 if steps else None),
                   "late_p99_ms": timing.percentile(late, 99),
                   "compiles": run.compiles_in_window,
                   "peak_gb": system.memory_peak_bytes(1) / 1e9,
                   **e2e}
            if args.check:
                fin = [{"tokens": r["tokens"], "prompt_len": r["prompt_len"]}
                       for r in run.records if r["complete"]]
                sample = check.pick_sample(
                    fin, int(cfg["check"]["sample_requests"]), args.seed + n)
                logits_at = family.reference.logits_at
                out["gaps"] = check.served_gaps(logits_at, ses.w, cfg, sample)
                if args.check != "ref":
                    out["control"] = check.served_gaps(
                        logits_at, ses.w, cfg, sample, quant=args.check)
            print(json.dumps(harness._finite(out)), flush=True)
    ses.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())

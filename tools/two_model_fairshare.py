"""The reference's signature experiment on this framework's hardware:
TWO models served CONCURRENTLY with fair-time arbitration, on a real TPU
(round-3 VERDICT missing #3; round-5: asymmetric per-query cost with BOTH
jobs live in the captured arbitration view — round-4's capture drained
the first stream before the snapshot and paired near-equal-cost jobs, so
the ratio formula's signature unequal split never showed on hardware.
Reference: `mp4_report_group1.pdf` p.1-2, ratio formula
`mp4_machinelearning.py:504-514`, worked example 7/3).

Runs a 3-node in-proc cluster on the visible chip (the reference used 10
VMs; XLA serializes the nodes' dispatches onto the one TPU, which is
exactly the fair-TIME-sharing regime the formula arbitrates), streams
HEAVY resnet50 queries (768 images each), starts a LIGHT alexnet stream
(192-image queries) mid-flight, and captures:

  - measured avg seconds/query per model (the formula's inputs — the
    ~4x per-query cost gap is what makes the fair share asymmetric),
  - the c1 allocation view POLLED while both jobs are in flight; the
    kept snapshot must contain BOTH jobs (the round-4 artifact's gap),
  - time from the second job's submission to its FIRST completed result
    (the reference measured 40-49 s for this, p.2 Fig 3),
  - per-model throughput while both streams are live.

Writes TWO_MODEL_FAIRSHARE.json (with the same self-verifying provenance
block bench.py stamps) and prints it. Usage:

    python tools/two_model_fairshare.py            # real TPU
    python tools/two_model_fairshare.py --cpu      # machinery dry-run
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

HEAVY, LIGHT = "resnet50", "alexnet"


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cpu", action="store_true",
                    help="dry-run the machinery on CPU (no artifact claim)")
    ap.add_argument("--heavy-images", type=int, default=768,
                    help=f"images per {HEAVY} query (batch-divisible so "
                         "each model compiles exactly one shape)")
    ap.add_argument("--light-images", type=int, default=192,
                    help=f"images per {LIGHT} query")
    ap.add_argument("--queries", type=int, default=6,
                    help="queries per model stream")
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--out", default=os.path.join(
        REPO, "TWO_MODEL_FAIRSHARE.json"))
    args = ap.parse_args()

    import jax
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")

    from bench import provenance
    from idunno_tpu.utils.compile_cache import enable_persistent_cache
    enable_persistent_cache()

    dev = jax.devices()[0]
    if not args.cpu and dev.platform != "tpu":
        print(json.dumps({"error": f"need a TPU, got {dev.platform}"}))
        return 2

    from idunno_tpu.comm.inproc import InProcNetwork
    from idunno_tpu.config import ClusterConfig, EngineConfig
    from idunno_tpu.serve.node import Node

    cfg = ClusterConfig(hosts=("n0", "n1", "n2"), coordinator="n0",
                        standby_coordinator="n1", introducer="n0",
                        replication_factor=2, ping_interval_s=0.2,
                        failure_timeout_s=2.0, metadata_interval_s=0.3,
                        query_batch_size=max(args.heavy_images,
                                             args.light_images))
    ecfg = EngineConfig(batch_size=args.batch, param_dtype="bfloat16")
    net = InProcNetwork()
    tmp = tempfile.mkdtemp(prefix="fairshare2m-")
    nodes = {h: Node(h, cfg, net.transport(h), os.path.join(tmp, h),
                     engine_config=ecfg) for h in cfg.hosts}
    n_img = {HEAVY: args.heavy_images, LIGHT: args.light_images}
    out: dict = {"platform": dev.platform,
                 "device_kind": getattr(dev, "device_kind", dev.platform),
                 "images_per_query": n_img, "batch": args.batch,
                 "engine_param_dtype": "bfloat16"}
    try:
        for n in nodes.values():
            n.start()
        deadline = time.time() + 10.0
        while time.time() < deadline and not all(
                len(n.membership.members.alive_hosts()) == 3
                for n in nodes.values()):
            time.sleep(0.05)
        master = nodes["n0"]
        svc = master.inference

        def submit(model):
            return svc.inference(model, 0, n_img[model] - 1)[0]

        def run_query(model):
            q = submit(model)
            while not svc.query_done(model, q):
                time.sleep(0.02)
            return q

        # warm both models (compile once per (model, batch) — persistent
        # cache makes the 3 nodes share compiled programs across runs)
        t0 = time.time()
        run_query(HEAVY)
        out[f"warm_{HEAVY}_s"] = round(time.time() - t0, 2)
        t0 = time.time()
        run_query(LIGHT)
        out[f"warm_{LIGHT}_s"] = round(time.time() - t0, 2)

        # the warm queries above paid each model's one-time compile; their
        # inflated per-query times must NOT feed the fair-share signal
        # (the reference's 7/3 worked example is a steady-state split, and
        # a compile-polluted avg buries it). Reset every node's timing
        # window so the arbitration view below sees only steady queries —
        # the CNN-side analogue of the LM tier's structural exclusion
        # (Completion.cold_start, serve/lm_manager.py:_drain skips those
        # samples), so both demand signals measure steady state.
        for n in nodes.values():
            n.inference.metrics.reset_processing()
            n.inference.scheduler.avg_query_time = {}

        # -- job 1 stream alone: measured rate -----------------------------
        t0 = time.time()
        for _ in range(2):
            run_query(HEAVY)
        out[f"{HEAVY}_alone_s_per_query"] = round((time.time() - t0) / 2, 3)

        # -- job 2 starts while job 1 has queries in flight -----------------
        r_qs = [submit(HEAVY) for _ in range(args.queries)]
        t_submit2 = time.time()
        a_first = submit(LIGHT)
        # the master submit path assigns + dispatches every task
        # synchronously before returning the qnum, so this stamp IS the
        # scheduling latency — isolated from the chip contention baked
        # into first_result on this rig (3 nodes multiplex ONE chip
        # while 6 heavy queries are in flight; the
        # reference's 40-49 s was job STARTUP — weight download+load — on
        # 10 parallel VMs, and FAIRSHARE.json measures this framework's
        # startup at ~1.4 s with compute mocked)
        out["second_job_first_task_dispatch_s"] = round(
            time.time() - t_submit2, 3)
        a_qs = [submit(LIGHT) for _ in range(args.queries - 1)]

        # poll the arbitration view while the streams drain, keeping every
        # snapshot in which BOTH jobs are live (after a stream drains it
        # rightly leaves active_models(), which is what blinded the
        # round-4 capture) — the LAST both-live snapshot has the most
        # timing history and is the one the artifact reports
        first_result_s = None
        both_live: list[dict] = []
        share_pairs: set[tuple[int, int]] = set()
        pending = {HEAVY: list(r_qs), LIGHT: [a_first, *a_qs]}
        t0 = time.time()
        while any(pending.values()):
            for m in (HEAVY, LIGHT):
                pending[m] = [q for q in pending[m]
                              if not svc.query_done(m, q)]
            if first_result_s is None and svc.query_done(LIGHT, a_first):
                first_result_s = round(time.time() - t_submit2, 3)
            view = master.lm_manager.allocation_view()
            jobs = view.get("jobs", {})
            if f"cnn:{HEAVY}" in jobs and f"cnn:{LIGHT}" in jobs:
                both_live.append(view)
                share_pairs.add((jobs[f"cnn:{HEAVY}"]["share"],
                                 jobs[f"cnn:{LIGHT}"]["share"]))
            time.sleep(0.2)
        dt = time.time() - t0
        out["second_job_first_result_s"] = first_result_s
        out["reference_second_job_first_result_s"] = "40-49 (p.2 Fig 3)"
        total_imgs = (len(r_qs) * n_img[HEAVY]
                      + (len(a_qs) + 1) * n_img[LIGHT])
        out["concurrent_images_per_s"] = round(total_imgs / dt, 1)
        out["allocation_live"] = (both_live[-1] if both_live
                                  else {"error": "no both-live snapshot"})
        out["both_live_snapshots"] = len(both_live)
        out["share_pairs_seen"] = sorted(share_pairs)
        ja = out["allocation_live"].get("jobs", {})
        out["asymmetric_split"] = bool(
            ja.get(f"cnn:{HEAVY}", {}).get("share", 0)
            != ja.get(f"cnn:{LIGHT}", {}).get("share", 0))
        # steady-state check (VERDICT item 4): with compile-window samples
        # excluded, the COSTLIER-per-query model must hold the LARGER
        # share in the captured both-live view — the ratio formula's
        # signature, provable only on a clean steady-state signal
        out["share_ordering_matches_cost"] = bool(
            ja.get(f"cnn:{HEAVY}", {}).get("share", 0)
            >= ja.get(f"cnn:{LIGHT}", {}).get("share", 0))

        # -- the arbitration inputs (c1 allocation view) -------------------
        out["avg_query_s"] = {
            m: round(t, 4)
            for m, t in svc.scheduler.avg_query_time.items()}
        from idunno_tpu.scheduler.fair import fair_shares
        out["fair_shares"] = fair_shares(
            svc.scheduler.avg_query_time, cfg.rate_factor, 3)
        # worker sets actually used by the LAST query of each stream
        out["workers_last_query"] = {
            HEAVY: sorted({t.worker for t in
                           svc.scheduler.book.tasks_for_query(
                               HEAVY, r_qs[-1])}),
            LIGHT: sorted({t.worker for t in
                           svc.scheduler.book.tasks_for_query(
                               LIGHT, a_qs[-1])}),
        }
        out["provenance"] = provenance()
        if not args.cpu:
            with open(args.out, "w") as f:
                json.dump(out, f, indent=1)
        print(json.dumps(out))
        return 0
    finally:
        for n in nodes.values():
            n.stop()


if __name__ == "__main__":
    sys.exit(main())

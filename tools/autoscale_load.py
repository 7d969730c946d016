"""Ramp→overload→underload load driver for the replica-group autoscaler.

Reuses `tools/gateway_load.py`'s open-loop Poisson machinery to offer
three regimes to gateway-fronted replica pools — ``ramp`` (0.8x measured
capacity), ``overload`` (2x) and ``underload`` (0.3x) — and then feeds
the MEASURED interactive queue-wait p95 of each regime to a real
`serve/autoscaler.py:Autoscaler` (manager stubbed by `PolicyProbe`), so
the record shows the decisions the closed loop takes on this exact
hardware: spawn at overload, drain-then-retire at underload.

The overload regime additionally runs in the scaled-OUT configuration
(two replica pools behind a round-robin `ReplicaRouter`, each with its
own gateway — the group's decode routing without the cluster) to measure
what the spawn buys: goodput gain and interactive p95 back under the
deadline slack.

Two consumers:

- `utils/lm_bench.py:run_lm_autoscale_bench` (``BENCH_SUITE=
  lm_autoscale``) imports
  `run_phases` / `probe_decisions` / `ReplicaRouter` for the live
  backend record.
- Standalone CLI for a quick CPU demo:

      python tools/autoscale_load.py --requests 36
"""
from __future__ import annotations

import argparse
import json
import os
import random
import sys
import time
from types import SimpleNamespace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from tools.gateway_load import (  # noqa: E402
    poisson_schedule, run_open_loop)

# (name, offered load as a multiple of measured capacity)
PHASES = (("ramp", 0.8), ("overload", 2.0), ("underload", 0.3))


class ReplicaRouter:
    """Round-robins submissions across replica loops with namespaced
    rids — the group's decode routing stripped of the cluster, so
    `run_open_loop` can drive N replicas as one target."""

    _BASE = 1_000_000

    def __init__(self, loops) -> None:
        self.loops = list(loops)
        self._i = 0

    def submit(self, prompt, max_new, **kw) -> int:
        i = self._i % len(self.loops)
        self._i += 1
        return i * self._BASE + self.loops[i].submit(prompt, max_new, **kw)

    def poll(self):
        out = []
        for i, lp in enumerate(self.loops):
            for c in lp.poll():
                ns = SimpleNamespace(**vars(c))
                ns.id = i * self._BASE + c.id
                out.append(ns)
        return out

    def stats(self) -> dict:
        """Worst-replica gateway percentiles per class — the same
        max-over-replicas reduction the autoscaler's `_p95` applies."""
        classes: dict = {}
        for lp in self.loops:
            gw = lp.stats().get("gateway")
            if not gw:
                continue
            for p, c in gw["classes"].items():
                cur = classes.get(p)
                if (cur is None or c["queue_wait_s"].get("p95", 0.0)
                        > cur["queue_wait_s"].get("p95", 0.0)):
                    classes[p] = c
        return {"gateway": {"classes": classes}} if classes else {}


class PolicyProbe:
    """Minimal manager stand-in so the REAL `Autoscaler` control loop
    decides on measured gauges: the group_* mutations record decisions
    instead of placing pools. Shapes mirror `LMPoolManager.group_view`."""

    def __init__(self, policy) -> None:
        self.policy = policy
        self.replicas = {"grp@r0": {"state": "active", "role": "decode",
                                    "t_drain": 0.0}}
        self._next = 1
        self.t_last_decision = 0.0
        self.decisions: list[dict] = []
        self.gauges: dict = {}
        self.now = 0.0

    def group_names(self):
        return ["grp"]

    def group_view(self, name):
        return {"policy": self.policy,
                "replicas": {r: dict(m, undelivered=0)
                             for r, m in self.replicas.items()},
                "t_last_decision": self.t_last_decision,
                "route_counts": {"total": 0, "prefill": 0},
                "debts": {}}

    def group_gauges(self, name):
        return dict(self.gauges)

    def _record(self, action: str, **attrs) -> dict:
        d = {"action": action, "t": round(self.now, 3), **attrs}
        self.decisions.append(d)
        self.t_last_decision = self.now
        return d

    def group_spawn(self, name, role="decode", **attrs):
        r = f"grp@r{self._next}"
        self._next += 1
        self.replicas[r] = {"state": "active", "role": role,
                            "t_drain": 0.0}
        return self._record("spawn", replica=r, role=role, **attrs)

    def group_retire_start(self, name, replica=None, **attrs):
        active = [r for r, m in self.replicas.items()
                  if m["state"] == "active"]
        if len(active) <= 1:
            return None
        victim = replica if replica is not None else max(active)
        self.replicas[victim].update(state="draining", t_drain=self.now)
        return self._record("retire_start", replica=victim, **attrs)

    def group_retire(self, name, replica):
        if self.replicas.get(replica, {}).get("state") != "draining":
            return None
        del self.replicas[replica]
        return self._record("retire", replica=replica)

    def group_rebalance(self, name):
        return None


def probe_decisions(phase_p95: dict[str, float],
                    slack_s: float) -> dict:
    """Drive the real autoscaler through the measured regimes (one tick
    per phase on a fake clock, plus a drain tick) and return the
    decision stream — the record's proof of WHAT the loop does with
    these gauges on this hardware."""
    from idunno_tpu.serve.autoscaler import Autoscaler, AutoscalePolicy

    policy = AutoscalePolicy(deadline_slack_s=slack_s, scale_in_frac=0.5,
                             dwell_s=1.0, drain_window_s=1.0,
                             max_replicas=2)
    probe = PolicyProbe(policy)
    auto = Autoscaler(probe, clock=lambda: probe.now)
    for i, (phase, _) in enumerate(PHASES):
        probe.now = 10.0 * (i + 1)
        # backlog 0: every phase drains fully, so p95 vs the slack is
        # the whole signal (the cumulative-window regime the scale-in
        # disjunction exists for)
        probe.gauges = {r: {"interactive_p95": phase_p95[phase], "n": 8,
                            "backlog": 0}
                        for r, m in probe.replicas.items()
                        if m["state"] == "active"}
        auto.tick()
    probe.now += 10.0        # past the drain window: retire completes
    auto.tick()
    return {"policy": {"deadline_slack_s": round(slack_s, 4),
                       "max_replicas": policy.max_replicas},
            "decisions": probe.decisions}


def interactive_p95(rec: dict) -> float:
    return float(((rec.get("queue_wait_s") or {})
                  .get("interactive") or {}).get("p95", 0.0))


def run_phases(make_loop, capacity_rps: float, *, n_requests: int,
               prompt_fn, max_new: int, seed: int = 0,
               deadline: float | None = None,
               scaled_overload: bool = True) -> dict:
    """The three offered-load regimes against one replica, plus the
    overload regime against TWO replicas behind a router. ``make_loop``
    builds a fresh gateway-fronted loop per phase (matching how every
    group replica owns its own gateway)."""
    out: dict = {}
    for i, (phase, multiple) in enumerate(PHASES):
        if deadline is not None and time.perf_counter() > deadline \
                and phase != "overload":
            continue        # the overload record is the headline
        loop = make_loop()
        try:
            sched = poisson_schedule(capacity_rps * multiple, n_requests,
                                     random.Random(seed + i))
            rec = run_open_loop(loop, sched, prompt_fn=prompt_fn,
                                max_new=max_new)
        finally:
            loop.stop()
        rec["load_multiple"] = multiple
        out[phase] = rec
    if scaled_overload:
        loops = [make_loop(), make_loop()]
        router = ReplicaRouter(loops)
        try:
            sched = poisson_schedule(capacity_rps * 2.0, n_requests,
                                     random.Random(seed + 1))
            rec = run_open_loop(router, sched, prompt_fn=prompt_fn,
                                max_new=max_new)
        finally:
            for lp in loops:
                lp.stop()
        rec["load_multiple"] = 2.0
        rec["replicas"] = 2
        out["overload_scaled"] = rec
    return out


def summarize(phases: dict) -> dict:
    """The scale-out story in four numbers + the probed decisions."""
    over = phases.get("overload", {})
    scaled = phases.get("overload_scaled", {})
    p95_before = interactive_p95(over)
    p95_after = interactive_p95(scaled)
    # Clockwork-style deadline slack, set between the measured regimes
    # so the record is robust to box speed: the overload regime breaches
    # it, the ramp regime (plus 10% headroom — if noise inverts the
    # regimes the probe honestly records NO decisions rather than a
    # scrambled spawn-at-ramp story) does not
    ramp_p95 = interactive_p95(phases.get("ramp", {}))
    slack = max(1e-3, 1.1 * ramp_p95, (ramp_p95 + p95_before) / 2.0)
    out = {"deadline_slack_s": round(slack, 4),
           "interactive_p95_1_replica": round(p95_before, 4),
           "interactive_p95_2_replicas": round(p95_after, 4),
           "slo_recovered": bool(p95_after <= slack < p95_before)}
    if over.get("goodput_rps") and scaled.get("goodput_rps"):
        out["goodput_gain"] = round(
            scaled["goodput_rps"] / max(over["goodput_rps"], 1e-9), 2)
    out.update(probe_decisions(
        {"ramp": ramp_p95, "overload": p95_before,
         "underload": interactive_p95(phases.get("underload", {}))},
        slack_s=slack))
    return out


def _make_loop_factory(slots: int):
    from tools.gateway_load import _build_pool

    def make_loop():
        server, wrap = _build_pool(
            slots, {"max_queue": 4 * slots,
                    "batch_wait_slack": 1.0,
                    "interactive_wait_slack": 3.0})
        return wrap(server)
    return make_loop


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--requests", type=int, default=36)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    rng = random.Random(args.seed)
    make_loop = _make_loop_factory(args.slots)

    # closed-loop capacity on a throwaway replica sizes the offers
    loop = make_loop()
    prompts = [[rng.randrange(1, 128) for _ in range(16)]
               for _ in range(4 * args.slots)]
    t0 = time.perf_counter()
    for p in prompts:
        loop.submit(p, max_new=args.max_new)
    drained: set[int] = set()
    while len(drained) < len(prompts):
        drained.update(c.id for c in loop.poll())
        time.sleep(0.002)
    capacity_rps = len(prompts) / (time.perf_counter() - t0)
    loop.stop()

    phases = run_phases(
        make_loop, capacity_rps, n_requests=args.requests,
        prompt_fn=lambda: [rng.randrange(1, 128) for _ in range(16)],
        max_new=args.max_new, seed=args.seed)
    print(json.dumps({"capacity_rps": round(capacity_rps, 2),
                      "phases": phases,
                      "autoscale": summarize(phases)}))


if __name__ == "__main__":
    main()

"""Open-loop Poisson load generator for the QoS admission gateway.

Offers traffic to an `LMServingLoop` fronted by a
`serve/gateway.py:AdmissionGateway` the way a population of independent
clients would: arrivals follow a Poisson process pinned to wall-clock
offsets, and a submission is NEVER delayed by earlier requests'
completions (open loop — the arrival rate does not self-throttle under
overload, which is exactly the regime admission control exists for).
Each arrival draws a tenant/priority from a configurable mix, so one run
exercises quotas, weighted fair queueing and class-ordered dispatch at
once.

Two consumers:

- `utils/lm_bench.py:run_lm_gateway_bench` (``BENCH_SUITE=lm_gateway``)
  imports `poisson_schedule` / `run_open_loop` to measure goodput vs
  offered load and shed rate on the live backend.
- Standalone CLI for a quick CPU-mesh overload demo:

      python tools/gateway_load.py --load 2.0 --requests 48

  builds a tiny in-process pool, measures its closed-loop capacity, then
  offers ``--load`` x capacity through the gateway and prints one JSON
  record (interactive vs batch outcomes, queue-wait percentiles, shed
  reasons).
"""
from __future__ import annotations

import argparse
import json
import os
import random
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

# (tenant, priority, weight-in-mix, deadline_ms) — the default mix pairs a
# latency-sensitive interactive tenant against bulk batch traffic, the
# protect-the-interactive-class scenario the backpressure slacks encode
DEFAULT_MIX = (
    ("ivy", "interactive", 0.5, None),
    ("bulk", "batch", 0.5, None),
)


def poisson_schedule(rate_per_s: float, n: int, rng: random.Random,
                     mix=DEFAULT_MIX) -> list[tuple]:
    """``n`` arrivals as (t_offset_s, tenant, priority, deadline_ms),
    exponential inter-arrival gaps at ``rate_per_s``, mix drawn per
    arrival by weight. Deterministic under a seeded rng — the bench's
    offered load is reproducible run to run."""
    tenants = [m[0] for m in mix]
    weights = [m[2] for m in mix]
    by_tenant = {m[0]: m for m in mix}
    out, t = [], 0.0
    for _ in range(n):
        t += rng.expovariate(rate_per_s)
        tenant = rng.choices(tenants, weights=weights)[0]
        _, priority, _, deadline_ms = by_tenant[tenant]
        out.append((t, tenant, priority, deadline_ms))
    return out


def run_open_loop(loop, schedule, *, prompt_fn, max_new: int,
                  drain_timeout_s: float = 120.0,
                  poll_interval_s: float = 0.005) -> dict:
    """Offer ``schedule`` to ``loop`` open-loop and drain to completion.

    Returns per-class outcome counts (admitted / shed-by-reason /
    expired / completed), offered vs goodput request rates, goodput
    tokens/sec (generated tokens of non-rejected completions over the
    offer+drain wall clock), and the gateway's own queue-wait
    percentiles at the end of the run."""
    from idunno_tpu.serve.admission import AdmissionShed

    classes: dict[str, dict] = {}

    def cls(priority: str) -> dict:
        return classes.setdefault(priority, {
            "offered": 0, "admitted": 0, "expired": 0, "completed": 0,
            "shed": {}})

    completions: dict[int, object] = {}
    admitted: dict[int, str] = {}            # rid -> priority

    def drain_polls() -> None:
        for c in loop.poll():
            completions[c.id] = c

    t0 = time.perf_counter()
    for t_off, tenant, priority, deadline_ms in schedule:
        while True:
            now = time.perf_counter() - t0
            if now >= t_off:
                break
            drain_polls()
            time.sleep(min(poll_interval_s, t_off - now))
        c = cls(priority)
        c["offered"] += 1
        try:
            rid = loop.submit(prompt_fn(), max_new, tenant=tenant,
                              priority=priority, deadline_ms=deadline_ms)
            admitted[rid] = priority
            c["admitted"] += 1
        except AdmissionShed as e:
            c["shed"][e.reason] = c["shed"].get(e.reason, 0) + 1
    offer_s = time.perf_counter() - t0

    deadline = time.perf_counter() + drain_timeout_s
    while (len(completions.keys() & admitted.keys()) < len(admitted)
           and time.perf_counter() < deadline):
        drain_polls()
        time.sleep(poll_interval_s)
    drain_polls()
    total_s = time.perf_counter() - t0

    goodput_tokens = 0
    for rid, priority in admitted.items():
        comp = completions.get(rid)
        if comp is None:
            continue
        if getattr(comp, "rejected", None) == "expired":
            cls(priority)["expired"] += 1
            continue
        cls(priority)["completed"] += 1
        goodput_tokens += len(comp.tokens) - comp.prompt_len

    n_offered = len(schedule)
    n_shed = sum(sum(c["shed"].values()) for c in classes.values())
    n_completed = sum(c["completed"] for c in classes.values())
    out = {
        "offered": n_offered,
        "offered_rps": round(n_offered / max(offer_s, 1e-9), 2),
        "goodput_rps": round(n_completed / max(total_s, 1e-9), 2),
        "tokens_per_s": round(goodput_tokens / max(total_s, 1e-9), 1),
        "shed_rate": round(n_shed / max(n_offered, 1), 3),
        "offer_s": round(offer_s, 3),
        "total_s": round(total_s, 3),
        "classes": classes,
    }
    gw = loop.stats().get("gateway")
    if gw:
        out["queue_wait_s"] = {p: c["queue_wait_s"]
                               for p, c in gw["classes"].items()}
    return out


def _build_pool(slots: int, gateway_spec: dict):
    """Tiny CPU-friendly pool fronted by a gateway (CLI path)."""
    import jax
    import jax.numpy as jnp

    from idunno_tpu.engine.serve_lm import DecodeServer
    from idunno_tpu.models.transformer import TransformerLM
    from idunno_tpu.serve.gateway import AdmissionGateway
    from idunno_tpu.serve.lm_pool import LMServingLoop

    model = TransformerLM(vocab=128, dim=64, depth=1, num_heads=4,
                          causal=True)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    server = DecodeServer(model, params, slots=slots, prompt_len=16,
                          max_len=48)
    server.warmup()
    return server, lambda srv: LMServingLoop(
        srv, name="gateway-load", gateway=AdmissionGateway(gateway_spec))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--load", type=float, default=2.0,
                    help="offered load as a multiple of measured capacity")
    ap.add_argument("--requests", type=int, default=48,
                    help="arrivals to offer")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    rng = random.Random(args.seed)
    server, make_loop = _build_pool(args.slots, {})

    # closed-loop capacity: drain a saturating batch with no gateway
    prompts = [[rng.randrange(1, 128) for _ in range(16)]
               for _ in range(4 * args.slots)]
    t0 = time.perf_counter()
    for p in prompts:
        server.submit(p, max_new=args.max_new)
    server.run_until_drained()
    cap_s = time.perf_counter() - t0
    capacity_rps = len(prompts) / cap_s

    loop = make_loop(server)
    sched = poisson_schedule(capacity_rps * args.load, args.requests, rng)
    rec = run_open_loop(
        loop, sched,
        prompt_fn=lambda: [rng.randrange(1, 128) for _ in range(16)],
        max_new=args.max_new)
    loop.stop()
    rec = {"capacity_rps": round(capacity_rps, 2),
           "load_multiple": args.load, **rec}
    print(json.dumps(rec))


if __name__ == "__main__":
    main()

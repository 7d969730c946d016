"""One run of one cell: set-up, warm-up, the measured window, the check
against the plain reference, the result line."""
from __future__ import annotations

import gc
import json
import os
import shutil
import sys
import time
from dataclasses import dataclass, field

from benchmark import check, manifest, timing, traffic
from benchmark.loadgen import Generator
from benchmark.timing import clock

TRACE_S = 6.0            # length of the traced part of a --trace 1 window
DRAIN_S = 60.0           # how long a request due in the window may take
_COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",)
_CACHE_EVENTS = ("/jax/compilation_cache/cache_hits",)


@dataclass
class RunData:
    """What the per-layer readers read (see `benchmark/readers`)."""
    cfg: dict
    device: dict
    family: object = None    # `Manifest.family`: counts, reference, ...
    w0: float = 0.0
    w1: float = 0.0
    tw0: float = 0.0
    tw1: float = 0.0
    steps: list = field(default_factory=list)
    records: list = field(default_factory=list)
    spans: list = field(default_factory=list)
    stats0: dict = field(default_factory=dict)
    stats1: dict = field(default_factory=dict)
    modules: dict = field(default_factory=dict)   # program -> [s, runs]
    ops: dict = field(default_factory=dict)       # '<program>/<op>' -> s
    busy_s: float = 0.0
    trace_window_s: float = 0.0
    compiles_in_window: int = 0
    memory_peak_bytes: int = 0
    step_contexts: list = field(default_factory=list)
    window_step_contexts: list = field(default_factory=list)
    traced_prefills: list = field(default_factory=list)
    window_prefills: list = field(default_factory=list)
    breakdown: dict = field(default_factory=dict)


class CompileCounter:
    """Counts JAX's compile events (a backend compile, or a program fetched
    from the persistent cache) while `counting` is set."""

    def __init__(self):
        import jax
        self.count = 0
        self.counting = False
        self.what: list = []
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._ev)

    def _dur(self, event, _secs, **kw):
        if self.counting and event in _COMPILE_EVENTS:
            self.count += 1
            self.what.append(str(kw.get("fun_name", "?")))

    def _ev(self, event, **_kw):
        if self.counting and event in _CACHE_EVENTS:
            self.count += 1


def cache_dir() -> str:
    """JAX's persistent cache: where JAX_COMPILATION_CACHE_DIR says, else a
    fixed directory inside the checkout (the path is part of the key)."""
    import jax
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not env:
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(manifest.ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return jax.config.jax_compilation_cache_dir


def _wait(pred, timeout: float, what: str, loop=None) -> None:
    end = clock() + timeout
    while not pred():
        if clock() > end:
            errs = loop.errors() if loop is not None else []
            raise RuntimeError(f"timed out after {timeout:.0f}s waiting for "
                               f"{what}; pool errors: {errs[:3]}")
        time.sleep(0.01)


def _serve_all(loop, reqs, timeout: float, what: str) -> list:
    """Submit ``reqs`` and wait for every completion (set-up traffic)."""
    ids = {loop.submit(r.tokens, r.max_new) for r in reqs}
    got = []
    def done():
        got.extend(loop.poll())
        return ids <= {c.id for c in got}
    _wait(done, timeout, what, loop)
    return got


def _shape_of(req, serving) -> tuple[int, int]:
    """(prefix hit, suffix bucket) that admitting ``req`` compiles for."""
    rest = len(req.tokens) - req.shared_len
    bucket = next(b for b in sorted(serving["prompt_buckets"]) if b >= rest)
    return req.shared_len, bucket


def warm_shapes(loop, tr, cfg, rng) -> int:
    """One throwaway request for every (prefix hit, suffix bucket) the
    stream will meet, so that the window compiles nothing: each prefill
    bucket, each prefix-hit suffix shape with its insert and block write,
    the decode dispatch. Returns how many were sent."""
    serving, vocab = cfg["serving"], cfg["vocab_size"]
    seen, warmers = set(), []
    for r in tr.requests:
        shape = _shape_of(r, serving)
        if shape in seen:
            continue
        seen.add(shape)
        # a full bucket of private tokens: at least one whole block of
        # them, so the block write of this row length compiles too
        own = rng.integers(0, vocab, shape[1]).tolist()
        warmers.append(traffic.Request(
            index=-1, tokens=r.tokens[:r.shared_len] + own,
            max_new=serving["decode_steps"] + 1, due_s=None))
    _serve_all(loop, warmers, 1100.0, "the shape warm-up")
    return len(warmers)


class Session:
    """The system under test, built and warmed once: weights from the seed,
    the pool as `lm_serve` builds it, the step stamps, every shape the
    traffic will meet. `window` then measures one window of a stream."""

    def __init__(self, cfg: dict, family, mix: dict, seed: int,
                 seconds: float, *, trace_on: bool, rehearse: bool,
                 device: dict):
        import jax
        import numpy as np

        from benchmark import system

        self.cfg, self.family, self.mix = cfg, family, mix
        self.device = device
        self.trace_on = trace_on
        self.compiles = CompileCounter()
        self.parts = {"imports_and_chip_s": clock()}   # since process start
        if rehearse and "max_rps_hint" in mix:   # the toy model is fast
            mix = dict(mix, max_rps_hint=40 * mix["max_rps_hint"])
        self.shrink = (16, 4) if rehearse else (1, 1)
        self.tr = traffic.generate(mix, cfg["serving"], cfg["vocab_size"],
                                   seed, seconds, shrink=self.shrink)
        t = clock()
        self.w = jax.block_until_ready(
            family.weights.make_weights(cfg, seed))
        self.parts["weights_s"] = clock() - t
        t = clock()
        self.spans = system.span_store(clock) if trace_on else None
        self.loop, self.server = system.build(cfg, self.w, family,
                                              spans=self.spans)
        self._gen: list = []
        self.sc = timing.StepClock(
            self.server,
            on_done=lambda: self._gen and self._gen[0].done_event.set())
        rng = np.random.default_rng([int(seed), 0x5E7])
        jax.block_until_ready(self.server._tokens)
        self.parts["pool_build_s"] = clock() - t
        t = clock()
        if self.tr.setup_requests:
            _serve_all(self.loop, self.tr.setup_requests, 1100.0,
                       "the shared prefixes")
        self.parts["shared_prefixes_s"] = clock() - t
        t = clock()
        self.n_warm = warm_shapes(self.loop, self.tr, cfg, rng)
        jax.block_until_ready(self.server._tokens)
        self.parts["warm_shapes_s"] = clock() - t
        self.parts["warm_traffic_s"] = self.tr.warm_s

    def window(self, tr, seconds: float) -> tuple:
        """Drive ``tr`` (warm-up traffic, then ``seconds`` of window) and
        return (RunData, generator, completions, marks, trace_dir)."""
        import jax

        loop, mix = self.loop, tr.mix
        trace_ids = iter(range(1 << 30))
        stamp = ((lambda: (f"t:bench:{next(trace_ids)}", "root"))
                 if self.trace_on else None)
        t_start = clock() + 0.05
        w0, w1 = t_start + tr.warm_s, t_start + tr.warm_s + seconds
        gen = Generator(loop, tr, t_start=t_start, t_end=w1,
                        trace_stamp=stamp)
        self._gen[:] = [gen]
        run = RunData(cfg=self.cfg, device=self.device, family=self.family,
                      w0=w0, w1=w1)
        first_step = len(self.sc.steps)
        gen.start()
        time.sleep(max(0.0, w0 - clock()))
        self.compiles.count, self.compiles.what = 0, []
        self.compiles.counting = True
        run.stats0 = loop.stats()
        trace_dir = os.path.join(manifest.ROOT, ".trace",
                                 f"bench-{os.getpid()}")
        marks: list[float] = []
        if self.trace_on:
            tlen = min(TRACE_S, seconds / 2)
            time.sleep(max(0.0, w0 + (seconds - tlen) / 2 - clock()))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            for _ in range(2):
                with jax.profiler.TraceAnnotation("bench.mark"):
                    marks.append(clock())
                time.sleep(0.001)
            run.tw0 = marks[0]
            time.sleep(max(0.0, run.tw0 + tlen - clock()))
            with jax.profiler.TraceAnnotation("bench.mark"):
                marks.append(clock())
            run.tw1 = marks[-1]
            jax.profiler.stop_trace()
        time.sleep(max(0.0, w1 - clock()))
        run.stats1 = loop.stats()
        self.compiles.counting = False
        run.compiles_in_window = self.compiles.count
        gen.join(timeout=5.0)
        gen.stop()
        if gen.failure is not None:
            raise RuntimeError("the load generator failed") from gen.failure
        # after the close: wait for what was due in the window
        completions = list(gen.completions)
        if mix["loop"] == "open":
            want = {rid for rid, (_r, due, _s) in gen.submitted.items()
                    if due < w1}
            def drained():
                completions.extend(loop.poll())
                return want <= {c.id for c in completions}
            try:
                _wait(drained, DRAIN_S, "requests due in the window", loop)
            except RuntimeError as e:
                print(f"benchmark: {e}", file=sys.stderr)
        run.steps = self.sc.steps[first_step:]
        run.window_step_contexts = [
            (t0, t1, live, ctx) for t0, t1, live, _p, ctx in run.steps
            if w0 <= t1 < w1]
        return run, gen, completions, marks, trace_dir

    def records(self, run: RunData, gen, completions) -> tuple[int, int]:
        """Fill ``run.records`` (one per request due in the window) and
        return (attempted, failed)."""
        w0, w1 = run.w0, run.w1
        by_id = {c.id: c for c in completions}
        times = timing.request_times(run.steps)
        for rid, (req, due, sent) in sorted(gen.submitted.items()):
            if not (w0 <= due < w1):
                continue
            rec = {"req": req, "rid": rid, "due": due, "sent": sent}
            rec.update(times.get(rid, {}))
            c = by_id.get(rid)
            rec["complete"] = bool(
                c is not None and not c.cancelled
                and len(c.tokens) - c.prompt_len == req.max_new)
            if c is not None:
                rec["tokens"] = c.tokens
                rec["prompt_len"] = c.prompt_len
            run.records.append(rec)
        for idx, due, _msg in gen.errors:
            if w0 <= due < w1:
                run.records.append({"req": gen.traffic.requests[idx],
                                    "due": due, "complete": False})
        if self.mix["loop"] == "open":
            # due in the window, waited for past its close: whatever is
            # still not whole has failed
            failed = sum(1 for r in run.records if not r["complete"])
        else:
            # a client's last request is cut by the window's end, not lost:
            # only a refused submit fails
            failed = sum(1 for r in run.records if r["rid"] is None)
        return len(run.records), failed

    def close(self) -> list:
        """Stop the pool and free its state; the weights stay for the
        reference. Returns the pool's errors."""
        self.loop.stop(timeout=30.0)
        errors = self.loop.errors()
        self.sc.remove()
        for g in self._gen:
            g.loop = None
        self._gen.clear()
        self.loop = self.server = self.sc = None
        gc.collect()
        return errors


def run(args, t_proc: float) -> tuple[dict, dict]:
    """One run of one cell: (the result line's object, a summary of the
    window). Keys of the summary that start with `_` are for tests."""
    from benchmark import system, trace as tracemod

    root = getattr(args, "root", None)
    man = (manifest.Manifest(root, os.path.join(root, "benchmark"))
           if root else manifest.Manifest())
    cell = man.cell(args.workload)
    cfg = man.config(cell)
    family = man.family(cfg)
    cfg = system.model_config(cfg, args.rehearse, family)
    mix = man.mix(cell)
    device = system.require_chips(cell["chips"], args.rehearse)
    if not args.rehearse:
        cache_dir()
    seconds = float(args.seconds)
    trace_on = bool(args.trace)
    ses = Session(cfg, family, mix, args.seed, seconds, trace_on=trace_on,
                  rehearse=args.rehearse, device=device)
    ses.parts["imports_and_chip_s"] -= t_proc
    run, gen, completions, marks, trace_dir = ses.window(ses.tr, seconds)
    setup_s = run.w0 - t_proc
    peak = system.memory_peak_bytes(device["count"])
    run.memory_peak_bytes = peak
    run.spans = ses.spans.dump() if ses.spans is not None else []
    attempted, failed = ses.records(run, gen, completions)
    w, n_warm, tr = ses.w, ses.n_warm, ses.tr
    compiled = ses.compiles.what[:8]
    errors = ses.close()
    e2e = timing.end_to_end(run.records, run.steps, run.w0, run.w1)
    e2e["setup_s"] = setup_s
    late = [(r["sent"] - r["due"]) * 1e3 for r in run.records
            if "sent" in r]
    summary = {
        "workload": args.workload, "seed": args.seed, "seconds": seconds,
        "requests_due": attempted, "completed": sum(
            1 for r in run.records if r["complete"]), "failed": failed,
        "gen_lateness_p99_ms": _finite(timing.percentile(late, 99)),
        "gen_lateness_max_ms": max(late) if late else None,
        "warm_requests": n_warm, "setup_requests": len(tr.setup_requests),
        "compiles_in_window": run.compiles_in_window, "compiled": compiled,
        "pool_errors": errors[:3], "setup_parts": ses.parts,
        "all": _finite(e2e)}
    print(json.dumps(summary), flush=True)

    # -- the check against the plain reference -----------------------------
    finished = [{"tokens": r["tokens"], "prompt_len": r["prompt_len"]}
                for r in run.records if r["complete"]
                and r["req"].temperature == 0.0]
    chk = cfg["check"]
    sample = check.pick_sample(finished, int(chk["sample_requests"]),
                               args.seed)
    t_chk = clock()
    gaps = check.served_gaps(family.reference.logits_at, w, cfg, sample)
    limit = (cfg["rehearse"]["check_limit"] if args.rehearse
             else chk["limit"])
    limit = float("nan") if limit is None else limit
    numbers = {"max_gap": (gaps["max_gap"], limit),
               "unfinished": (float(failed), 0.0)}
    correct, compared = check.decide(numbers)
    compared["checked_tokens"] = {"value": gaps["tokens"], "limit": None}
    compared["mean_gap"] = {"value": gaps["mean_gap"], "limit": None}
    compared["agree"] = {"value": gaps["agree"], "limit": None}
    compared["check_s"] = {"value": clock() - t_chk, "limit": None}
    if args.control:
        # the control, for setting the limit; a measured run never asks
        ctl = check.served_gaps(family.reference.logits_at, w, cfg, sample,
                                quant=args.control)
        print(json.dumps({"control": args.control, **_finite(ctl)}),
              flush=True)

    # -- metrics -----------------------------------------------------------
    metrics = {}
    breakdown = None
    if trace_on:
        try:
            fill_trace(run, tracemod, trace_dir, marks, args.rehearse)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        for m in man.per_layer(args.workload):
            fn, kw = man.reader(m["name"])
            try:
                val = fn(run, **kw)
            except KeyError:
                if not args.rehearse:    # the CPU is in no table of peaks
                    raise
                val = None
            if val is not None:
                metrics[m["name"]] = {"value": val, "unit": m["unit"]}
        breakdown = run.breakdown
        device = dict(device, busy_s=run.busy_s, window_s=run.trace_window_s)
    else:
        for m in man.end_to_end(args.workload):
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    device = dict(device, memory_peak_bytes=peak)
    if args.rehearse:
        # a rehearsal's numbers are the CPU's: never under a device
        # metric's name, and never `correct: true`
        metrics = {"rehearse." + k: v for k, v in metrics.items()}
    result = {"correct": correct and not args.rehearse,
              "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    if args.rehearse:
        result["rehearse"] = True
        result["verdict_at_toy_size"] = correct
    result["compared"] = compared
    summary.update(_sample=sample, _weights=w, _cfg=cfg, _limit=limit,
                   _family=family)
    return _finite(result), summary


def run_cell(args, t_proc: float) -> int:
    """`run`, printed as the contract has it: each number compared beside
    its limit as the last lines of standard error, the result as the last
    line of standard output."""
    result, _summary = run(args, t_proc)
    for k, v in result["compared"].items():
        print(f"compared {k}: value {v['value']} limit {v['limit']}",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0 if not args.rehearse else 3


def _finite(x):
    """JSON has no NaN or infinity: they become null."""
    if isinstance(x, float) and (x != x or x in (float("inf"),
                                                  float("-inf"))):
        return None
    if isinstance(x, dict):
        return {k: _finite(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_finite(v) for v in x]
    return x


def fill_trace(run: RunData, tracemod, trace_dir: str, marks: list,
               rehearse: bool = False) -> None:
    """Reduce the profiler's trace and the program's spans into what the
    readers read, and the breakdown."""
    tr = tracemod.load_xplane(tracemod.find_xplane(trace_dir))
    hm = [e for e in tr["host"] if e[0] == "bench.mark"]
    if len(hm) < 2:
        raise RuntimeError("trace holds no bench.mark annotation")
    if not tr["devices"] and not rehearse:   # the CPU has no device plane
        raise RuntimeError("trace holds no device plane")
    # host clock -> trace clock, from the first mark
    off = hm[0][1] - marks[0]
    a, b = hm[0][1], hm[-1][1]
    tr = tracemod.clip(tr, a, b)
    keep = os.environ.get("BENCH_KEEP_TRACE")
    if keep:      # a short piece of the reduced trace, for the tests' file
        tracemod.save(tracemod.clip(tr, a, a + 0.35), keep)
    run.trace_window_s = b - a
    run.busy_s = tracemod.busy_seconds(tr)
    run.modules = tracemod.module_times(tr)
    host = []
    for s in run.spans:
        if s["name"] in ("lm.prefill", "lm.prefill_chunk", "lm.decode_step"):
            host.append((s["name"], s["t_start"] + off, s["t_end"] + off))
    prev_end = None
    for t0, t1, live, _p, _ctx in run.steps:
        host.append(("step.other", t0 + off, t1 + off))
        if prev_end is not None and t0 > prev_end:
            host.append(("loop.between_steps" if live else "loop.idle_wait",
                         prev_end + off, t0 + off))
        prev_end = t1
    # earlier names win an overlap: a span inside a step names that part
    order = {"lm.prefill": 0, "lm.prefill_chunk": 0, "lm.decode_step": 1,
             "step.other": 2, "loop.between_steps": 3, "loop.idle_wait": 3}
    host.sort(key=lambda s: order[s[0]])
    gaps = tracemod.idle_gaps(tr, a, b, host)
    run.ops = tracemod.op_times(tr)
    run.breakdown = {"device_ops": tracemod.top(run.ops),
                     "idle_gaps": tracemod.top(gaps)}
    run.step_contexts = [(t0, t1, live, ctx) for t0, t1, live, _p, ctx
                         in run.steps if run.tw0 <= t1 < run.tw1]
    for s in run.spans:
        if s["name"] != "lm.prefill":
            continue
        at = s["attrs"]
        pre = (at["prompt_len"] - at["prefix_hit"], at["prefix_hit"])
        if run.w0 <= s["t_end"] < run.w1:
            run.window_prefills.append(pre)
        if run.tw0 <= s["t_end"] < run.tw1:
            run.traced_prefills.append(pre)

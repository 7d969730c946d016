"""The device's idle time, put down to the program's own phases.

Every span of the pool loop's timeline (`loop.iter` and its children,
`lm.step` and its children) is also a profiler annotation of the same name,
so the profiler's trace holds it beside the device's operations. The two
clocks of that trace disagree by about 0.9 ms on the chip, so the
annotations are first moved by the least offset under which no decode
dispatch starts before its enqueue (`device_lag`). Then each idle instant of
the first device inside the traced window goes to the INNERMOST annotation
on the loop's thread that covers it; where none does, to the request lane's
`lm.prefill` / `lm.prefill_chunk` spans placed through the offset of the
first `bench.mark`; else `unattributed`. Gaps under 2 us lie between the
operations of one program (`device.between_ops`).

`python3 benchmark/phases.py <run.py's arguments> --trace 1` is one traced
run of a cell whose result line's breakdown holds `idle_phases` (the top
ten), `longest_idle`, `device_lag_ms` and `phase_clock_skew_ms` beside
what `benchmark/run.py` gives; with `BENCH_KEEP_TRACE` set it keeps a
short piece of the reduced trace with the annotations under `program`.
Folding `fill_phases` into `harness.fill_trace` is a `benchmark` PR's
(PERF.md §7)."""
from __future__ import annotations

import bisect
import os
import sys

if __package__ in (None, ""):     # run as a script from the checkout
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from benchmark import run as run_py  # noqa: E402  (first: the process clock)
from benchmark import trace  # noqa: E402

PROGRAM = ("lm.", "loop.", "kv.", "state.")   # the program's annotations
LOOP_MARKS = ("loop.iter", "lm.step")          # what names the loop thread
REQUEST_LANE = ("lm.prefill", "lm.prefill_chunk")
PAIR_S = 0.005     # a dispatch and its enqueue lie closer than this; two
                   # dispatches lie a step (over 10 ms) apart


def load_program(path: str) -> list[tuple]:
    """(name, start, seconds, thread) of the program's annotations in the
    profiler's `.xplane.pb`, in order of start; ``thread`` tells the host
    threads apart (plane and line)."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for i, line in enumerate(plane.lines):
            thread = f"{plane.name}#{i}"
            out.extend((ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9,
                        thread) for ev in line.events
                       if ev.name.startswith(PROGRAM))
    out.sort(key=lambda e: e[1])
    return out


def clip(program: list[tuple], w0: float, w1: float) -> list[tuple]:
    """The annotations inside [w0, w1), cut at the edges (nesting holds)."""
    out = []
    for name, s, d, th in program:
        a, b = max(s, w0), min(s + d, w1)
        if b > a:
            out.append((name, a, b - a, th))
    return out


def innermost(events: list[tuple]) -> list[tuple[str, float, float]]:
    """(name, start, end) pieces of time, each under the innermost of the
    (name, start, seconds) ``events`` covering it. The events of one thread
    nest (a scoped annotation), and a child is cut to its parent."""
    out: list[tuple[str, float, float]] = []
    stack: list[tuple[float, str]] = []      # (end, name), outermost first
    at = 0.0

    def close_until(t):
        nonlocal at
        while stack and stack[-1][0] <= t:
            end, name = stack.pop()
            if end > at:
                out.append((name, at, end))
                at = end

    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        close_until(s)
        if stack and s > at:
            out.append((stack[-1][1], at, s))
        end = min(s + d, stack[-1][0]) if stack else s + d
        stack.append((end, name))
        at = s
    close_until(float("inf"))
    return [p for p in out if p[2] > p[1]]


def loop_events(program: list[tuple]) -> list[tuple[str, float, float]]:
    """(name, start, seconds) of the annotations on the loop's thread(s):
    those that hold a `loop.iter` or an `lm.step`."""
    threads = {th for name, _s, _d, th in program if name in LOOP_MARKS}
    return [(n, s, d) for n, s, d, th in program if th in threads]


def device_lag(tr: dict, program: list[tuple]) -> float:
    """Seconds by which the trace puts the device's events early against
    the host's annotations: the most by which a decode dispatch
    (`jit_run`) starts before the `lm.decode_step` annotation that
    enqueued it, which it cannot (a lower bound of the offset; 0 where no
    dispatch does). The profiler's two clocks disagree by about 0.9 ms on
    the chip (PERF.md §5)."""
    starts = sorted(s for name, s, _d, _th in program
                    if name == "lm.decode_step")
    if not starts or not tr["devices"]:
        return 0.0
    lag = 0.0
    for name, s, _d in tr["devices"][0]["lines"].get("XLA Modules", []):
        if name.split("(", 1)[0] != "jit_run":
            continue
        i = bisect.bisect_left(starts, s)
        near = min((starts[j] for j in (i - 1, i) if 0 <= j < len(starts)),
                   key=lambda t: abs(t - s))
        if abs(near - s) < PAIR_S:
            lag = max(lag, near - s)
    return lag


def idle_phases(tr: dict, program: list[tuple], w0: float, w1: float,
                request_lane: list[tuple[str, float, float]] = (),
                small: float = 2e-6) -> dict[str, float]:
    """Idle seconds of the first device inside [w0, w1) by the innermost
    program annotation on the loop thread; then the request lane's
    (name, start, end) spans on the trace's clock; then `unattributed`."""
    pieces = innermost(loop_events(program))
    return trace.idle_gaps(tr, w0, w1, pieces + list(request_lane), small)


def longest_gap(tr: dict, pieces: list[tuple], w0: float,
                w1: float) -> list | None:
    """[milliseconds, phase] of the longest idle stretch of the first device
    inside [w0, w1), the phase being the innermost piece that covers its
    middle (`unattributed` where none does): one stall of the host tells
    itself from a cost paid at every dispatch."""
    if not tr["devices"]:
        return None
    best, at = (0.0, w0), w0
    for a, b in trace.busy_intervals(tr["devices"][0]) + [(w1, w1)]:
        if min(a, w1) - at > best[0]:
            best = (min(a, w1) - at, at)
        at = max(at, b)
        if at >= w1:
            break
    mid = best[1] + best[0] / 2
    name = next((n for n, a, b in pieces if a <= mid < b), "unattributed")
    return [best[0] * 1e3, name]


def clock_skew_ms(program: list[tuple], spans: list[dict], off: float,
                  w0: float, w1: float) -> float | None:
    """The largest distance between the start of an `lm.step` span that
    lies inside [w0, w1) (its `t_start` + ``off``; the profiler records an
    annotation as it ends) and the nearest start of an `lm.step`
    annotation: whether the two clocks agree at both ends of the window."""
    starts = [s for name, s, _d, _th in program if name == "lm.step"]
    worst = None
    for sp in spans:
        t = sp["t_start"] + off
        if (sp["name"] != "lm.step" or not starts or sp["t_end"] is None
                or not w0 <= t <= sp["t_end"] + off < w1):
            continue
        i = bisect.bisect_left(starts, t)
        near = min(abs(starts[j] - t) for j in (i - 1, i)
                   if 0 <= j < len(starts))
        worst = near if worst is None else max(worst, near)
    return None if worst is None else worst * 1e3


def fill_phases(run, tr: dict, program: list[tuple], marks: list) -> None:
    """``run.phases`` and the breakdown's `idle_phases`, `longest_idle`,
    `device_lag_ms` and `phase_clock_skew_ms`, from the whole trace as
    `trace.load_xplane` gives it and the program's annotations
    (`load_program`), these moved onto the device's time by `device_lag`;
    the window and the spans' offset are `harness.fill_trace`'s, from the
    `bench.mark`s."""
    hm = [e for e in tr["host"] if e[0] == "bench.mark"]
    off = hm[0][1] - marks[0]
    a, b = hm[0][1], hm[-1][1]
    lag = device_lag(tr, program)     # host annotations -> device's time
    inside = clip([(n, s - lag, d, th) for n, s, d, th in program], a, b)
    lane = [(s["name"], s["t_start"] + off - lag, s["t_end"] + off - lag)
            for s in run.spans
            if s["name"] in REQUEST_LANE and s["t_end"] is not None]
    cut = trace.clip(tr, a, b)
    run.phases = idle_phases(cut, inside, a, b, lane)
    run.breakdown["idle_phases"] = trace.top(run.phases)
    run.breakdown["longest_idle"] = longest_gap(
        cut, innermost(loop_events(inside)), a, b)
    run.breakdown["device_lag_ms"] = lag * 1e3
    run.breakdown["phase_clock_skew_ms"] = clock_skew_ms(
        program, run.spans, off, a, b)
    keep = os.environ.get("BENCH_KEEP_TRACE")
    if keep:      # a short piece with the annotations, for the tests' file
        piece = trace.clip(cut, a, a + 0.35)
        piece["program"] = clip(program, a, a + 0.35)
        trace.save(piece, keep)


def main() -> int:
    from benchmark import harness

    plain = harness.fill_trace

    def fill_trace(run, tracemod, trace_dir, marks, rehearse=False):
        plain(run, tracemod, trace_dir, marks, rehearse)
        path = trace.find_xplane(trace_dir)
        fill_phases(run, trace.load_xplane(path), load_program(path), marks)

    harness.fill_trace = fill_trace
    return run_py.main()


if __name__ == "__main__":
    sys.exit(main())

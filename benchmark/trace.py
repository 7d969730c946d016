"""Reduction from a profiler trace to numbers.

`load_xplane` turns the profiler's `.xplane.pb` into a small plain structure
(device planes with their "XLA Ops" and "XLA Modules" lines, and the host's
`bench.*` annotations), everything below works on that structure, and a small
recorded one is kept in `benchmark/data/` for the tests. Times are seconds on
the trace's own clock.
"""
from __future__ import annotations

import bisect
import glob
import gzip
import json
import os

# region ops whose span covers the ops inside them: counting them beside
# their leaves would double the time
_WRAPPERS = ("while", "conditional", "call", "tuple")


def find_xplane(trace_dir: str) -> str:
    hits = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))
    if not hits:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return hits[-1]


def _short(name: str) -> str:
    """'%fusion.295 = bf16[...] fusion(...)' -> 'fusion.295'."""
    head = name.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def _module_name(name: str) -> str:
    """'jit__write_block(1234567)' -> 'jit__write_block'."""
    return name.split("(", 1)[0].strip()


def load_xplane(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = {"devices": [], "host": []}
    for plane in data.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            lines = {}
            for line in plane.lines:
                if line.name in ("XLA Ops", "XLA Modules"):
                    lines[line.name] = [
                        (ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9)
                        for ev in line.events]
            if "XLA Ops" in lines:
                out["devices"].append({"name": plane.name, "lines": lines})
        elif not plane.name.startswith("/device:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        out["host"].append((ev.name, ev.start_ns * 1e-9,
                                            ev.duration_ns * 1e-9))
    out["host"].sort(key=lambda e: e[1])
    return out


def save(tr: dict, path: str) -> None:
    with gzip.open(path, "wt", encoding="utf-8") as f:
        json.dump(tr, f)


def load(path: str) -> dict:
    with gzip.open(path, "rt", encoding="utf-8") as f:
        tr = json.load(f)
    tr["host"] = [tuple(e) for e in tr["host"]]
    for d in tr["devices"]:
        d["lines"] = {k: [tuple(e) for e in v] for k, v in d["lines"].items()}
    return tr


def clip(tr: dict, w0: float, w1: float) -> dict:
    """The part of a trace inside [w0, w1): events are cut at the edges."""
    def cut(evs):
        out = []
        for name, s, d in evs:
            a, b = max(s, w0), min(s + d, w1)
            if b > a:
                out.append((name, a, b - a))
        return out
    return {"host": cut(tr["host"]),
            "devices": [{"name": d["name"],
                         "lines": {k: cut(v) for k, v in d["lines"].items()}}
                        for d in tr["devices"]]}


def _leaf_ops(dev: dict) -> list[tuple]:
    return [(n, s, d) for n, s, d in dev["lines"]["XLA Ops"]
            if _short(n).split(".")[0] not in _WRAPPERS]


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def busy_intervals(dev: dict) -> list[tuple[float, float]]:
    return _union([(s, s + d) for _n, s, d in _leaf_ops(dev)])


def busy_seconds(tr: dict) -> float:
    """Seconds in which an operation ran on the device, averaged over the
    device planes."""
    per = [sum(b - a for a, b in busy_intervals(d)) for d in tr["devices"]]
    return sum(per) / len(per) if per else 0.0


def _ops_by_module(tr: dict):
    """(program, op, start, seconds) of every leaf op on the first device
    plane; the program is the "XLA Modules" event the op starts in."""
    if not tr["devices"]:
        return
    dev = tr["devices"][0]
    mods = sorted((s, s + d, _module_name(n))
                  for n, s, d in dev["lines"].get("XLA Modules", []))
    starts = [m[0] for m in mods]
    for n, s, d in _leaf_ops(dev):
        i = bisect.bisect_right(starts, s) - 1
        mod = mods[i][2] if i >= 0 and s < mods[i][1] else "(no module)"
        yield mod, _short(n), s, d


def module_times(tr: dict) -> dict[str, list]:
    """{program: [seconds, runs]} over the first device plane: the device
    time of each jitted program, from its ops (a module's own span includes
    the waits inside it)."""
    out: dict[str, list] = {}
    if tr["devices"]:
        for n, _s, _d in tr["devices"][0]["lines"].get("XLA Modules", []):
            out.setdefault(_module_name(n), [0.0, 0])[1] += 1
    for mod, _op, _s, d in _ops_by_module(tr):
        out.setdefault(mod, [0.0, 0])[0] += d
    return out


def op_times(tr: dict) -> dict[str, float]:
    """{'<program>/<op>': seconds} of leaf ops on the first device plane."""
    out: dict[str, float] = {}
    for mod, op, _s, d in _ops_by_module(tr):
        out[f"{mod}/{op}"] = out.get(f"{mod}/{op}", 0.0) + d
    return out


def idle_gaps(tr: dict, w0: float, w1: float,
              host_spans: list[tuple[str, float, float]],
              small: float = 2e-6) -> dict[str, float]:
    """Idle seconds of the first device inside [w0, w1), by what the host
    was doing. ``host_spans`` holds (name, start, end) in order of
    precedence: a gap is given to the first spans that cover it, what none
    covers is `unattributed`. Gaps shorter than ``small`` lie between the
    operations of one program and are summed as `device.between_ops`."""
    out: dict[str, float] = {}
    if not tr["devices"]:
        return out
    gaps, at = [], w0
    for a, b in busy_intervals(tr["devices"][0]):
        if a > at:
            gaps.append((at, min(a, w1)))
        at = max(at, b)
        if at >= w1:
            break
    if at < w1:
        gaps.append((at, w1))
    classes: dict[str, list] = {}
    for name, a, b in host_spans:
        classes.setdefault(name, []).append((a, b))
    for name in classes:
        classes[name] = _union(classes[name])
    for ga, gb in gaps:
        if gb - ga < small:
            if gb > ga:
                out["device.between_ops"] = (
                    out.get("device.between_ops", 0.0) + gb - ga)
            continue
        free = [(ga, gb)]
        for name, ivs in classes.items():
            if not free:
                break
            starts = [iv[0] for iv in ivs]
            nxt = []
            for fa, fb in free:
                i = max(0, bisect.bisect_right(starts, fa) - 1)
                cur = fa
                while i < len(ivs) and ivs[i][0] < fb:
                    a, b = max(ivs[i][0], cur), min(ivs[i][1], fb)
                    if b > a:
                        if a > cur:
                            nxt.append((cur, a))
                        out[name] = out.get(name, 0.0) + (b - a)
                        cur = b
                    i += 1
                if cur < fb:
                    nxt.append((cur, fb))
            free = nxt
        rest = sum(b - a for a, b in free)
        if rest > 0:
            out["unattributed"] = out.get("unattributed", 0.0) + rest
    return out


def top(d: dict[str, float], n: int = 10) -> list[list]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]

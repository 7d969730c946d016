"""Stamps and the arithmetic of the end-to-end metrics.

The stamps are made by the pool loop's own thread: `StepClock` wraps
`DecodeServer.step` (the program has no stamp of a request's first or last
token) and notes, at the end of each step, how many tokens every live or
just-retired request has produced. A step ends after the host has read the
rows' cursors back, so a token counted at a stamp was on the host by then -
it is the moment a streaming client could first have seen it. One clock
(`time.perf_counter`) serves the stamps and the generator's due times.

The metrics are over the whole window: every request due in it, every token
stamped in it, all of its seconds. None is a median of chunks.
"""
from __future__ import annotations

import math
import time

clock = time.perf_counter


class StepClock:
    """Wrapper round `DecodeServer.step`. Per step it appends
    ``(t0, t1, rows, progress, contexts)``: ``progress`` maps a server-side
    request id to the tokens it has generated so far, for every row live at
    the end of the step and every row the step retired; ``rows`` counts
    them and ``contexts`` holds the tokens each had in its cache."""

    def __init__(self, server, on_done=None):
        self.server = server
        self.steps: list[tuple] = []
        self._inner = server.step
        self._on_done = on_done
        server.step = self._step

    def _step(self) -> int:
        srv = self.server
        t0 = clock()
        out = self._inner()
        t1 = clock()
        progress, contexts = {}, []
        if srv._live:
            remaining, cursors = srv._remaining_cursors()
            for slot, req in srv._live.items():
                progress[req.id] = req.max_new - int(remaining[slot])
                contexts.append(int(cursors[slot]) + 1)
        done = srv._done
        for c in done:
            progress[c.id] = len(c.tokens) - c.prompt_len
            contexts.append(len(c.tokens))
        if progress or self.steps and self.steps[-1][3]:
            self.steps.append((t0, t1, len(contexts), progress, contexts))
        if done and self._on_done is not None:
            self._on_done()
        return out

    def remove(self) -> None:
        self.server.step = self._inner


def request_times(steps: list[tuple]) -> dict[int, dict]:
    """Per request id: the stamp of its first visible token(s) and how many
    there were, the stamp of its last, the total, and the step's start at
    which it was admitted."""
    out: dict[int, dict] = {}
    for t0, t1, _live, progress, _ctx in steps:
        for rid, n in progress.items():
            r = out.get(rid)
            if r is None:
                out[rid] = {"t_admit": t0, "t_first": t1, "n_first": n,
                            "t_last": t1, "n": n}
            elif n > r["n"]:
                r["t_last"], r["n"] = t1, n
    return out


def tokens_in(steps: list[tuple], w0: float, w1: float) -> int:
    """Output tokens whose stamp falls in [w0, w1)."""
    seen: dict[int, int] = {}
    total = 0
    for _t0, t1, _live, progress, _ctx in steps:
        for rid, n in progress.items():
            new = n - seen.get(rid, 0)
            seen[rid] = n
            if w0 <= t1 < w1:
                total += new
    return total


def percentile(values: list[float], q: float) -> float:
    """The ``q``-th percentile by the nearest-rank rule (no interpolation:
    a tail is one request's reading). An infinite value stands for a
    request that failed."""
    if not values:
        return float("nan")
    xs = sorted(values)
    k = max(0, min(len(xs) - 1, math.ceil(q / 100.0 * len(xs)) - 1))
    return xs[k]


def end_to_end(records: list[dict], steps: list[tuple], w0: float,
               w1: float) -> dict:
    """The window's end-to-end readings. ``records`` holds one entry per
    request due (open loop) or submitted (closed loop) in the window:
    ``due`` and, once served, ``t_first``, ``n_first``, ``t_last``, ``n``;
    a request without ``t_first`` failed and counts as the worst."""
    ttft, tpot = [], []
    for r in records:
        if r.get("t_first") is None:
            ttft.append(math.inf)
            tpot.append(math.inf)
            continue
        ttft.append((r["t_first"] - r["due"]) * 1e3)
        if r.get("complete") and r["n"] > r["n_first"]:
            tpot.append((r["t_last"] - r["t_first"]) * 1e3
                        / (r["n"] - r["n_first"]))
    return {"ttft_p90_ms": percentile(ttft, 90),
            "tpot_p90_ms": percentile(tpot, 90),
            "ttft_p50_ms": percentile(ttft, 50),
            "tpot_p50_ms": percentile(tpot, 50),
            "out_tok_s": tokens_in(steps, w0, w1) / (w1 - w0)}

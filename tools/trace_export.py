"""Chrome/Perfetto trace-event export for idunno_tpu span dumps.

Converts the span lists produced by `utils/spans.py` (node-local
``spans_dump`` windows, the cluster-merged ``trace`` verb reply, or a chaos
``last_span_dump``) into Chrome trace-event JSON — loadable in
ui.perfetto.dev or chrome://tracing, one process lane per node — and back.

The mapping is lossless: spans become ``ph:"X"`` complete events (µs
timestamps rebased to the trace start; the absolute base rides in
``otherData.t_base``), still-open spans become ``ph:"i"`` instants, span /
parent / trace ids ride in ``args`` next to the attrs (attrs therefore must
not use the reserved keys ``trace_id``/``span_id``/``parent`` — no
instrumentation site does), and node names ride ``process_name`` metadata
events. ``from_chrome`` inverts all of it; ``--selftest`` asserts the
round-trip is exact on a synthetic two-node trace.

CLI (always prints ONE JSON line, bench.py-style):

    python tools/trace_export.py --selftest
    python tools/trace_export.py --in trace_reply.json --out perfetto.json
    python tools/trace_export.py --capture
        # run one traced request through a real DecodeServer+LMServingLoop
        # on the default backend and write TRACE_WATERFALL.json (waterfall
        # rows + the Perfetto doc + provenance)
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

_RESERVED = ("trace_id", "span_id", "parent")


def to_chrome(spans: list[dict], trace_id: str | None = None) -> dict:
    """Span wire dicts -> Chrome trace-event document (one pid per node)."""
    spans = [dict(s) for s in spans
             if trace_id is None or s["trace_id"] == trace_id]
    base = min((s["t_start"] for s in spans), default=0.0)
    nodes = sorted({s["node"] for s in spans})
    pid = {n: i + 1 for i, n in enumerate(nodes)}
    events: list[dict] = [
        {"name": "process_name", "ph": "M", "pid": pid[n], "tid": 0,
         "args": {"name": n}} for n in nodes]
    for s in spans:
        args = {"trace_id": s["trace_id"], "span_id": s["span_id"]}
        if s.get("parent") is not None:
            args["parent"] = s["parent"]
        args.update(s.get("attrs") or {})
        ev = {"name": s["name"], "cat": "span", "pid": pid[s["node"]],
              "tid": 0, "ts": round((s["t_start"] - base) * 1e6, 3),
              "args": args}
        if s.get("t_end") is None:           # still-open span: instant
            ev.update(ph="i", s="t")
        else:
            ev.update(ph="X",
                      dur=round((s["t_end"] - s["t_start"]) * 1e6, 3))
        events.append(ev)
    return {"traceEvents": events, "displayTimeUnit": "ms",
            "otherData": {"t_base": base}}


def from_chrome(doc: dict) -> list[dict]:
    """Chrome trace-event document -> span wire dicts (inverse of
    `to_chrome`, exact for documents it produced)."""
    base = float((doc.get("otherData") or {}).get("t_base", 0.0))
    names = {e["pid"]: e["args"]["name"] for e in doc["traceEvents"]
             if e.get("ph") == "M" and e.get("name") == "process_name"}
    out = []
    for e in doc["traceEvents"]:
        if e.get("cat") != "span":
            continue
        args = dict(e.get("args") or {})
        tid = args.pop("trace_id")
        sid = args.pop("span_id")
        parent = args.pop("parent", None)
        t0 = round(base + e["ts"] / 1e6, 6)
        out.append({"trace_id": tid, "span_id": sid, "parent": parent,
                    "name": e["name"], "node": names.get(e["pid"], "?"),
                    "t_start": t0,
                    "t_end": (round(t0 + e["dur"] / 1e6, 6)
                              if e.get("ph") == "X" else None),
                    "attrs": args})
    return out


def waterfall(trace_id: str, spans: list[dict]) -> dict:
    """ONE-JSON-line waterfall of a trace: rows sorted by start offset,
    durations in ms — the machine-readable twin of the shell's `trace`
    command output."""
    spans = sorted((s for s in spans if s["trace_id"] == trace_id),
                   key=lambda s: (s["t_start"], s["span_id"]))
    base = min((s["t_start"] for s in spans), default=0.0)
    end = max((s["t_end"] for s in spans if s.get("t_end") is not None),
              default=base)
    rows = [{"name": s["name"], "node": s["node"],
             "offset_ms": round((s["t_start"] - base) * 1000.0, 3),
             "ms": (round((s["t_end"] - s["t_start"]) * 1000.0, 3)
                    if s.get("t_end") is not None else None),
             "parent": s.get("parent"),
             "attrs": s.get("attrs") or {}} for s in spans]
    return {"trace_id": trace_id, "spans": len(rows),
            "nodes": sorted({s["node"] for s in spans}),
            "duration_ms": round((end - base) * 1000.0, 3),
            "rows": rows}


def selftest() -> dict:
    """Synthetic two-node trace -> Perfetto doc -> back; asserts the
    round-trip reproduces every span exactly (fast lane, no jax)."""
    from idunno_tpu.utils.spans import SpanStore

    clk = {"t": 100.0}
    a = SpanStore("node-a", clock=lambda: clk["t"])
    b = SpanStore("node-b", clock=lambda: clk["t"])
    root = a.start("client.op", attrs={"kind": "selftest"})
    clk["t"] += 0.005
    child = b.start("server.handle", trace=root.trace_id,
                    parent=root.span_id, attrs={"hop": 1})
    clk["t"] += 0.010
    b.record("server.step", trace=root.trace_id, parent=child.span_id,
             attrs={"i": 0})
    clk["t"] += 0.002
    b.finish(child, rows=3)
    clk["t"] += 0.001
    a.finish(root, ok=True)
    spans = a.dump() + b.dump()
    # a still-open span exercises the instant-event path
    spans.append({"trace_id": root.trace_id, "span_id": "node-a:99",
                  "parent": root.span_id, "name": "still.open",
                  "node": "node-a", "t_start": round(clk["t"], 6),
                  "t_end": None, "attrs": {}})
    doc = to_chrome(spans, trace_id=root.trace_id)
    back = from_chrome(doc)
    key = lambda s: s["span_id"]  # noqa: E731
    assert sorted(back, key=key) == sorted(spans, key=key), \
        "round-trip mismatch"
    wf = waterfall(root.trace_id, spans)
    assert wf["spans"] == len(spans) and wf["nodes"] == ["node-a", "node-b"]
    return {"selftest": "ok", "spans": len(spans),
            "events": len(doc["traceEvents"]),
            "duration_ms": wf["duration_ms"]}


def capture(out_path: str = "TRACE_WATERFALL.json",
            max_new: int = 16) -> dict:
    """Run one traced request through a real continuous-batching pool on
    the default backend and write the waterfall + Perfetto doc (the
    artifact's provenance names the platform it ran on)."""
    import random

    import jax
    import jax.numpy as jnp

    from idunno_tpu.engine.serve_lm import DecodeServer
    from idunno_tpu.models.transformer import TransformerLM
    from idunno_tpu.serve.lm_pool import LMServingLoop
    from idunno_tpu.utils.spans import SpanStore

    platform = jax.default_backend()
    store = SpanStore("bench")
    model = TransformerLM(vocab=128, dim=64, depth=2, num_heads=4,
                          causal=True)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    server = DecodeServer(model, params, slots=4, prompt_len=16, max_len=48)
    server.warmup()      # compiles paid OFF the trace: spans time serving
    loop = LMServingLoop(server, name="trace-capture", spans=store)
    rng = random.Random(0)
    root = store.start("lm.submit", attrs={"pool": "trace-capture"})
    rid = loop.submit([rng.randrange(1, 128) for _ in range(16)],
                      max_new, trace=root.ctx)
    done = {}
    deadline = time.monotonic() + 120.0
    while rid not in done and time.monotonic() < deadline:
        for c in loop.poll():
            done[c.id] = c
        time.sleep(0.002)
    store.finish(root, rid=rid)
    loop.stop()
    assert rid in done, "traced request never completed"
    spans = store.dump(trace_id=root.trace_id)
    wf = waterfall(root.trace_id, spans)
    try:
        commit = subprocess.run(["git", "rev-parse", "HEAD"], cwd=REPO,
                                capture_output=True, text=True,
                                timeout=30).stdout.strip()
    except Exception:  # noqa: BLE001
        commit = ""
    rec = {"provenance": {"recorded_at": time.time(),
                          "git_commit": commit, "platform": platform},
           "decode_steps": sum(s["attrs"]["steps"] for s in spans
                               if s["name"] == "lm.decode"),
           "waterfall": wf,
           "chrome": to_chrome(spans, trace_id=root.trace_id)}
    with open(os.path.join(REPO, out_path), "w") as f:
        json.dump(rec, f, indent=1)
    return {"captured": out_path, "platform": platform,
            "trace_id": wf["trace_id"], "spans": wf["spans"],
            "decode_steps": rec["decode_steps"],
            "duration_ms": wf["duration_ms"]}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--selftest", action="store_true")
    ap.add_argument("--capture", action="store_true")
    ap.add_argument("--in", dest="inp",
                    help="JSON file: a `trace` verb reply "
                         "({trace_id, spans}) or a bare span list")
    ap.add_argument("--out", default="TRACE_WATERFALL.json",
                    help="output path (--capture artifact or --in's "
                         "Perfetto doc)")
    args = ap.parse_args()
    if args.selftest:
        print(json.dumps(selftest()))
        return
    if args.capture:
        print(json.dumps(capture(args.out)))
        return
    if args.inp:
        with open(args.inp) as f:
            data = json.load(f)
        spans = data["spans"] if isinstance(data, dict) else data
        tid = data.get("trace_id") if isinstance(data, dict) else None
        doc = to_chrome(spans, trace_id=tid)
        with open(args.out, "w") as f:
            json.dump(doc, f, indent=1)
        print(json.dumps({"wrote": args.out,
                          "events": len(doc["traceEvents"])}))
        return
    ap.error("pass --selftest, --capture, or --in FILE")


if __name__ == "__main__":
    main()

"""Readers of what the program records of itself (its `utils/spans.py`
and `DecodeServer.stats()`): the stamps the pool puts on `lm.finish`, the
spans of an admission, the pool loop's own timeline (`loop.iter`, `lm.step`
and their children) and the eviction counters. A span counts where it ends
inside the window (a request's stamps: where it was submitted inside it); a
program that records no such span or counter reads as None."""
from benchmark.timing import percentile


def _in_window(run, name):
    return [s for s in run.spans if s["name"] == name
            and s["t_end"] is not None and run.w0 <= s["t_end"] < run.w1]


def _duration_ms(run, name, q):
    ms = [(s["t_end"] - s["t_start"]) * 1e3 for s in _in_window(run, name)]
    return percentile(ms, q) if ms else None


def first_token_p90_ms(run):
    """Submit to the end of the step that first showed the host tokens of
    the request, by the pool's own stamps on `lm.finish`. Counted are the
    requests SUBMITTED in the window, as `ttft_p90_ms` counts those due in
    it, so that the two tails are of the same requests."""
    ms = [(a["t_first"] - a["t_submit"]) * 1e3
          for a in (s["attrs"] for s in run.spans if s["name"] == "lm.finish")
          if a.get("t_first") is not None and a.get("t_submit") is not None
          and run.w0 <= a["t_submit"] < run.w1]
    return percentile(ms, 90) if ms else None


def slot_wait_p50_ms(run):
    """In the server's queue until a slot took the request."""
    return _duration_ms(run, "lm.slot_wait", 50)


def admission_host_p50_ms(run):
    """The host's time for one admission, lookup to slot splice."""
    return _duration_ms(run, "lm.prefill", 50)


def kv_insert_p50_ms(run):
    """The radix insert of an admission, with the block writes it
    enqueues and the evictions it forces."""
    return _duration_ms(run, "kv.insert", 50)


def evict_walk_per_block(run):
    """Tree nodes visited for each block evicted, over the window."""
    a = run.stats0.get("prefix_cache", {})
    b = run.stats1.get("prefix_cache", {})
    if "evict_nodes_walked" not in b:
        return None
    freed = b["evictions"] - a["evictions"]
    walked = b["evict_nodes_walked"] - a["evict_nodes_walked"]
    return walked / freed if freed else None


def step_host_ms(run):
    """Over the loop iterations that dispatched: the iteration's time less
    the `lm.step.sync` spans inside it, averaged. What is left is the host
    working while the chip has nothing queued behind what it runs."""
    step_iter = {s["span_id"]: s["parent"] for s in run.spans
                 if s["name"] == "lm.step"}
    synced, dispatched = {}, set()
    for s in run.spans:
        it = step_iter.get(s["parent"])
        if it is None:
            continue
        if s["name"] == "lm.step.sync":
            synced[it] = synced.get(it, 0.0) + s["t_end"] - s["t_start"]
        elif s["name"] == "lm.decode_step":
            dispatched.add(it)
    host = [(s["t_end"] - s["t_start"] - synced.get(s["span_id"], 0.0)) * 1e3
            for s in _in_window(run, "loop.iter")
            if s["span_id"] in dispatched]
    return sum(host) / len(host) if host else None

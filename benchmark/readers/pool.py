"""Readers of the pool loop, admission and the prefix cache: step stamps,
the program's `lm.queue_wait` spans and `DecodeServer.stats()`."""
from benchmark.timing import percentile


def queue_wait_p50_ms(run):
    waits = [(s["t_end"] - s["t_start"]) * 1e3 for s in run.spans
             if s["name"] == "lm.queue_wait"
             and run.w0 <= s["t_end"] < run.w1]
    return percentile(waits, 50) if waits else None


def admission_p50_ms(run):
    """Due time to the start of the step that admitted the request."""
    waits = [(r["t_admit"] - r["due"]) * 1e3 for r in run.records
             if r.get("t_admit") is not None]
    return percentile(waits, 50) if waits else None


def batch_occupancy(run):
    """Live rows over slots, averaged over the window's dispatches."""
    live = [s[2] for s in run.steps if run.w0 <= s[1] < run.w1 and s[2]]
    if not live:
        return None
    return sum(live) / len(live) / run.cfg["serving"]["slots"]


def prefix_hit_share(run):
    """Prompt tokens served from the radix cache over prompt tokens, over
    the window (`stats()` read at its two ends)."""
    a, b = run.stats0, run.stats1
    saved = (b["prefix_cache"]["cached_tokens_saved"]
             - a["prefix_cache"]["cached_tokens_saved"])
    prompt = sum(len(r["req"].tokens) for r in run.records
                 if r.get("t_admit") is not None)
    return saved / prompt if prompt else None

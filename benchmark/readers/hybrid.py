"""Readers of what a stack with block-sparse and recurrent layers records
of itself (`DecodeServer.stats()`'s counters, the `state.splice` span) and
of its chunked prefill in the device trace. A program that has no such
counter, span or program reads as None."""
from benchmark.timing import percentile


def _delta(run, key):
    if key not in run.stats1:
        return None
    return run.stats1[key] - run.stats0.get(key, 0)


def sparse_attended_share(run):
    """Tokens the sparse layers' queries attended over the tokens they had
    in context, summed over live rows and decode steps of the window. 1.0
    would mean that no context was long enough to select, or that the
    selection is off."""
    attended = _delta(run, "sparse_tokens_attended")
    context = _delta(run, "sparse_tokens_in_context")
    return attended / context if attended is not None and context else None


def recurrent_state_gb(run):
    """Recurrent state the slot cache holds (a float32 matrix a head a
    linear layer a slot), in GB."""
    held = run.stats1.get("recurrent_state_bytes")
    return held / 1e9 if held else None


def prefill_chunk_ms(run):
    """Device time of one `_prefill_chunk` program, averaged over its runs
    in the traced window: how long a chunk of a long prompt holds the chip
    between two decode dispatches."""
    secs, runs = run.modules.get("jit__prefill_chunk", (0.0, 0))
    return secs * 1e3 / runs if runs else None


def state_splice_p50_ms(run):
    """The host's time to enqueue the splice of an admitted row's state,
    pooled keys and K/V into its slot (`state.splice`, a child of
    `lm.prefill`)."""
    ms = [(s["t_end"] - s["t_start"]) * 1e3 for s in run.spans
          if s["name"] == "state.splice" and s["t_end"] is not None
          and run.w0 <= s["t_end"] < run.w1]
    return percentile(ms, 50) if ms else None

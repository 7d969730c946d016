"""Readers of the device trace and of JAX's own counters. The operations
and bytes a step needs are the family's (`run.family.counts`)."""
from benchmark import peaks

_PREFILL = ("jit__prefill", "jit__prefill_suffix", "jit__prefill_suffix_paged",
            "jit__prefill_chunk")
_DECODE = ("jit_run",)


def _time(run, names):
    """(device seconds, runs) of the named programs in the traced window."""
    hit = [run.modules[k] for k in names if k in run.modules]
    return sum(v[0] for v in hit), sum(v[1] for v in hit)


def device_idle_share(run):
    if not run.trace_window_s:
        return None
    return 1.0 - run.busy_s / run.trace_window_s


def kv_write_share(run):
    """Device time of the `_write_block` programs over the traced window."""
    t, n = _time(run, ("jit__write_block",))
    return t / run.trace_window_s if n and run.trace_window_s else None


def prefill_time_share(run):
    t, n = _time(run, _PREFILL)
    return t / run.busy_s if n and run.busy_s else None


def decode_step_ms(run):
    t, n = _time(run, _DECODE)
    steps = n * run.cfg["serving"]["decode_steps"]
    return t * 1e3 / steps if steps else None


def compiles_in_window(run):
    return float(run.compiles_in_window)


def peak_hbm_gb(run):
    return run.memory_peak_bytes / 1e9 if run.memory_peak_bytes else None


def decode_step_roofline(run):
    """The decode steps' least time (weights once, each live row's KV and
    activations, against the table's peaks) over the device time of the
    decode dispatches in the traced window."""
    t, n = _time(run, _DECODE)
    if not n:
        return None
    k = run.cfg["serving"]["decode_steps"]
    least = 0.0
    for _t0, _t1, _live, ctx in run.step_contexts:
        if not ctx:
            continue
        # the k steps of a dispatch see the rows grow by one token each
        f, b = run.family.counts.decode_step_work(
            run.cfg, [max(1.0, c - (k - 1) / 2) for c in ctx])
        p = peaks.peaks_for(run.device["kind"])
        least += k * max(f / p["flops_bf16"], b / p["hbm_bytes_per_s"])
    if not least:
        return None
    # the host's stamps and the trace cut the window a dispatch apart:
    # scale the least time to the dispatches the trace holds
    least *= n / len([s for s in run.step_contexts if s[3]])
    return 100.0 * least / t


def prefill_roofline(run):
    t, n = _time(run, _PREFILL)
    if not n or not run.traced_prefills:
        return None
    p = peaks.peaks_for(run.device["kind"])
    least = 0.0
    for new, cached in run.traced_prefills:
        f, b = run.family.counts.prefill_work(run.cfg, new, cached)
        least += max(f / p["flops_bf16"], b / p["hbm_bytes_per_s"])
    return 100.0 * least / t if least else None


def step_mfu(run):
    """2 x parameters x every token the window processed, prompt and
    output, plus attention's operations, over window seconds x peak."""
    flops = 0.0
    for new, cached in run.window_prefills:
        flops += run.family.counts.prefill_work(run.cfg, new, cached)[0]
    k = run.cfg["serving"]["decode_steps"]
    for _t0, t1, _live, ctx in run.window_step_contexts:
        if ctx:
            flops += k * run.family.counts.decode_step_work(
                run.cfg, [max(1.0, c - (k - 1) / 2) for c in ctx])[0]
    if not flops:
        return None
    return peaks.mfu(flops, run.w1 - run.w0, run.device["kind"],
                     run.device["count"])

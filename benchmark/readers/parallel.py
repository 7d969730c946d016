"""Reader of what a stack whose layers hold BOTH an attention mixer and a
state-space mixer moves for their caches in a decode step
(`DecodeServer.stats()`'s gauges `kv_cache_bytes` and
`recurrent_state_bytes`, the family's `counts.py`). A program that has no
such gauge, or a family whose counts do not tell the caches apart, reads as
None."""


def mixer_cache_share(run):
    """Of the bytes a decode step of the window moves, the share that is
    the two mixers' caches. The caches as the PROGRAM moves them: the keys
    and values it reads (the whole slot cache, `kv_cache_bytes`; where the
    program counts how far along the token axis its steps read,
    `decode_context_read` / `decode_context_held`, that share of it) and
    the recurrent state of every slot read and written
    (2 x `recurrent_state_bytes`). Everything else (the weights, the head,
    the rows' activations) as `counts.decode_step_work` has it for the
    window's live rows, less `counts.cache_bytes`, the caches' algorithmic
    least. A step that reads live rows or live context only shows here
    first."""
    kv = run.stats1.get("kv_cache_bytes")
    state = run.stats1.get("recurrent_state_bytes")
    counts = getattr(run.family, "counts", None)
    if kv is None or state is None or not hasattr(counts, "cache_bytes"):
        return None
    held = (run.stats1.get("decode_context_held", 0)
            - run.stats0.get("decode_context_held", 0))
    if held:
        kv *= (run.stats1["decode_context_read"]
               - run.stats0.get("decode_context_read", 0)) / held
    moved = kv + 2 * state
    other, steps = 0.0, 0
    for _t0, _t1, _live, ctx in run.window_step_contexts:
        if ctx:
            other += (counts.decode_step_work(run.cfg, ctx)[1]
                      - counts.cache_bytes(run.cfg, ctx))
            steps += 1
    if not steps:
        return None
    return moved / (moved + other / steps)

"""Readers of what a stack with routed experts counts of itself on the
device (`DecodeServer.stats()`'s `expert_*` counters, accumulated over the
decode steps and read when `stats()` is). A program that has no such
counter reads as None."""


def _delta(run, key):
    if key not in run.stats1:
        return None
    return run.stats1[key] - run.stats0.get(key, 0)


def _ratio(run, over, under):
    a, b = _delta(run, over), _delta(run, under)
    return a / b if a is not None and b else None


def expert_held_pick_share(run):
    """Token-picks that fell on the experts held here over the picks the
    live tokens made (tokens x experts a token), over the window's decode
    steps: the share of the router's width that this chip holds, if the
    routing is even. It checks the cut."""
    return _ratio(run, "expert_tokens_routed", "expert_tokens_offered")


def expert_load_max_over_mean(run):
    """Picks on a layer's busiest held expert over the mean of its held
    experts, the layers summed: 1.0 is an even load; the busiest expert
    is the one a grouped product waits for."""
    return _ratio(run, "expert_load_max", "expert_load_mean")


def experts_touched_share(run):
    """Held experts that took at least one pick in a decode step, over the
    held experts, averaged over the window's steps and the layers: the
    share of the experts' weights a step has to stream."""
    return _ratio(run, "experts_touched", "experts_touchable")

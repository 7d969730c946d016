"""The one general traffic generator. A mix is a data file of parameters
(`benchmark/traffic/<mix>.json`); this module turns a mix, a configuration's
serving limits, a seed and a length of time into the request list and, for
an open loop, every due time - before the window, as a pure function of its
arguments: the same seed gives the same bytes.

Steadiness: every seed gets the SAME multiset of prompt lengths, output
lengths, group draws and inter-arrival gaps (the distribution's quantiles at
evenly spaced probabilities) and only their order, and the token ids, come
from the seed; the order keeps every few dozen consecutive requests spread
over the whole distribution (`_spread`). Two seeds then differ in who queues
behind whom, not in how much work the window holds.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from statistics import NormalDist

import numpy as np


@dataclass
class Request:
    index: int
    tokens: list[int]
    max_new: int
    due_s: float | None        # open loop: seconds after the stream's start
    group: int = -1            # sharing group (repository), -1 = none
    shared_len: int = 0        # leading tokens shared with the group
    temperature: float = 0.0
    phase: str = "window"      # "warm" requests precede the window


@dataclass
class Traffic:
    mix: dict
    requests: list[Request]
    setup_requests: list[Request] = field(default_factory=list)
    clients: int = 0           # closed loop: concurrent clients
    warm_s: float = 0.0


def _quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` values of the distribution ``spec`` at evenly spaced
    probabilities, as integers within [min, max]."""
    p = (np.arange(n) + 0.5) / n
    kind = spec["dist"]
    lo, hi = spec.get("min"), spec.get("max")
    if kind == "uniform":
        x = lo + p * (hi - lo)
    elif kind == "loguniform":
        x = np.exp(math.log(lo) + p * (math.log(hi) - math.log(lo)))
    elif kind == "lognormal":
        nd = NormalDist()
        z = np.array([nd.inv_cdf(float(q)) for q in p])
        x = spec["median"] * np.exp(spec["sigma"] * z)
    elif kind == "choice":
        vals = np.asarray(spec["values"], float)
        x = vals[np.minimum((p * len(vals)).astype(int), len(vals) - 1)]
    elif kind == "fixed":
        x = np.full(n, float(spec["value"]))
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    if lo is not None:
        x = np.clip(x, lo, hi)
    return np.rint(x).astype(int)


def _spread(values: np.ndarray, rng, block: int = 32) -> np.ndarray:
    """``values`` (sorted quantiles) in an order drawn from ``rng`` in which
    every run of about ``block`` consecutive entries spans the whole
    distribution: block j holds every nb-th value from the j-th on, and is
    shuffled within. A window that holds a few blocks then holds the same
    work whatever the seed."""
    nb = max(1, -(-len(values) // block))
    return np.concatenate([rng.permutation(values[j::nb])
                           for j in rng.permutation(nb)])


def _gaps(mix: dict, n: int) -> np.ndarray:
    """``n`` inter-arrival gaps in seconds, summing to n / rate."""
    rate = float(mix["rate_rps"])
    arrivals = mix.get("arrivals", "poisson")
    p = (np.arange(n) + 0.5) / n
    if arrivals == "poisson":
        g = -np.log1p(-p)                  # exponential quantiles, mean ~1
    elif arrivals == "uniform":
        g = np.ones(n)
    else:
        raise ValueError(f"unknown arrival process {arrivals!r}")
    return g * (n / rate) / g.sum()


def _burst_warp(t: np.ndarray, burst: dict) -> np.ndarray:
    """Squeeze arrival times so that a share ``on_s / period_s`` of each
    period carries ``factor`` times the mean rate and the rest the
    remainder; the mean rate is unchanged."""
    period, on, factor = burst["period_s"], burst["on_s"], burst["factor"]
    hot = min(1.0, factor * on / period)        # share of arrivals in bursts
    phase, k = np.modf(t / period)
    inside = phase < hot
    warped = np.where(inside, phase / hot * on,
                      on + (phase - hot) / max(1e-9, 1 - hot) * (period - on))
    return (k + warped / period) * period


def _zipf_counts(groups: int, s: float, n: int) -> np.ndarray:
    w = 1.0 / np.arange(1, groups + 1) ** s
    exact = w / w.sum() * n
    base = np.floor(exact).astype(int)
    short = n - base.sum()
    base[np.argsort(-(exact - base))[:short]] += 1     # largest remainders
    return base


def _shrunk(spec: dict, by: float) -> dict:
    """A length distribution with every length divided by ``by``."""
    out = dict(spec)
    for k in ("min", "max", "median", "value"):
        if k in out:
            out[k] = max(1, round(out[k] / by))
    if "values" in out:
        out["values"] = [max(1, round(v / by)) for v in out["values"]]
    return out


def generate(mix: dict, serving: dict, vocab: int, seed: int,
             seconds: float, shrink: tuple[float, float] = (1, 1),
             prefix_seed: int | None = None) -> Traffic:
    """The whole stream for one run: warm-up traffic of the same mix for
    ``mix["warm_s"]`` seconds, then ``seconds`` of window. ``shrink``
    divides prompt and output lengths (a rehearsal at tiny widths)."""
    if shrink != (1, 1):
        mix = dict(mix, prompt=_shrunk(mix["prompt"], shrink[0]),
                   output=_shrunk(mix["output"], shrink[1]))
        if mix.get("sharing"):
            mix["sharing"] = dict(mix["sharing"], prefix=_shrunk(
                mix["sharing"]["prefix"], shrink[0]), setup_tail=max(
                1, round(mix["sharing"].get("setup_tail", 16) / 2)))
    rng = np.random.default_rng([int(seed), 0x7AFF1C])
    # "order": "fixed" replays one schedule (lengths, gaps and groups in one
    # order) under every seed, which then draws the token ids alone; the
    # default draws the order from the seed too
    order = (np.random.default_rng(0x0DDE5) if mix.get("order") == "fixed"
             else rng)
    loop = mix["loop"]
    warm_s = float(mix.get("warm_s", 0.0))
    span = warm_s + float(seconds)
    if loop == "open":
        n = max(1, int(round(float(mix["rate_rps"]) * span)))
        clients = 0
    elif loop == "closed":
        clients = (serving["slots"] if mix.get("clients", "slots") == "slots"
                   else int(mix["clients"]))
        n = clients + int(math.ceil(float(mix["max_rps_hint"]) * span))
    else:
        raise ValueError(f"loop must be open or closed, got {loop!r}")

    prompt_len = _spread(_quantiles(mix["prompt"], n), order)
    out_len = _spread(_quantiles(mix["output"], n), order)
    sharing = mix.get("sharing")
    group = np.full(n, -1)
    prefixes: list[list[int]] = []
    if sharing:
        g = int(sharing["groups"])
        # the shared prefixes have a generator of their own, so that a
        # sweep's later windows can repeat them under another stream
        prng = np.random.default_rng(
            [int(seed if prefix_seed is None else prefix_seed), 0x9EF1C5])
        plen = order.permutation(_quantiles(sharing["prefix"], g))
        prefixes = [prng.integers(0, vocab, int(m)).tolist() for m in plen]
        counts = _zipf_counts(g, float(sharing.get("zipf_s", 1.0)), n)
        # each group's requests are spread evenly over the stream, at a
        # phase drawn from the seed: a repository's users come back at
        # their own pace, and the coldest one is never away for longer
        # than its share of the traffic says
        where = np.concatenate([(np.arange(c) + order.uniform()) / max(c, 1)
                                for c in counts])
        group = np.repeat(np.arange(g), counts)[np.argsort(where,
                                                           kind="stable")]
    due = None
    if loop == "open":
        due = np.cumsum(_spread(_gaps(mix, n), order))
        due -= due[0] * 0.5
        if mix.get("burst"):
            due = _burst_warp(due, mix["burst"])
    temperature = float(mix.get("sampling", {}).get("temperature", 0.0))
    greedy_every = int(mix.get("sampling", {}).get("greedy_every", 1))

    max_prompt = max(serving["prompt_buckets"])
    reqs = []
    for i in range(n):
        own = rng.integers(0, vocab, int(prompt_len[i])).tolist()
        gi = int(group[i])
        toks = (prefixes[gi] + own) if gi >= 0 else own
        new = int(out_len[i])
        if len(toks) > max_prompt or len(toks) + new > serving["max_len"]:
            raise ValueError(
                f"request {i}: {len(toks)} prompt + {new} new tokens do not "
                f"fit prompt bucket {max_prompt} / max_len "
                f"{serving['max_len']}")
        t_due = float(due[i]) if due is not None else None
        phase = "window"
        if due is not None and t_due < warm_s:
            phase = "warm"
        reqs.append(Request(
            index=i, tokens=toks, max_new=new, due_s=t_due, group=gi,
            shared_len=len(prefixes[gi]) if gi >= 0 else 0,
            temperature=(0.0 if temperature == 0.0 or i % greedy_every == 0
                         else temperature),
            phase=phase))
    if loop == "closed":
        # the first `clients` requests stand for clients caught mid-request:
        # each gets a share of its output drawn from the seed, so the pool
        # starts at the mix of ages it holds in the steady state
        for r in reqs[:clients]:
            r.phase = "warm"
            r.max_new = max(1, int(round(r.max_new * order.uniform(0.05, 1.0))))
    setup = []
    if sharing and sharing.get("admit_in_setup", False):
        # one cold request a group: its prefix and one private block, so the
        # radix cache holds every prefix before the stream starts
        for gi, pre in enumerate(prefixes):
            tail = rng.integers(0, vocab, int(sharing.get("setup_tail", 16)))
            setup.append(Request(index=-1 - gi, tokens=pre + tail.tolist(),
                                 max_new=1, due_s=None, group=gi,
                                 shared_len=len(pre), phase="setup"))
    return Traffic(mix=mix, requests=reqs, setup_requests=setup,
                   clients=clients, warm_s=warm_s)


def stream_bytes(tr: Traffic) -> bytes:
    """The request list and every due time as bytes (for the test that the
    same seed gives the same bytes)."""
    parts = []
    for r in tr.setup_requests + tr.requests:
        parts.append(np.asarray(r.tokens, np.int32).tobytes())
        parts.append(np.asarray([r.max_new, r.group, r.shared_len],
                                np.int64).tobytes())
        parts.append(np.asarray([-1.0 if r.due_s is None else r.due_s,
                                 r.temperature], np.float64).tobytes())
    return b"".join(parts)

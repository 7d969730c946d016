"""The comparison that decides `correct` for a served model.

Once the window has closed, a sample of the requests it finished (the
longest among them, the rest drawn from the seed) goes through the plain
reference: one forward pass over each prompt with its served tokens. The
number compared is the widest gap by which a served token's reference logit
lies below the reference's best logit at that position. A sound bf16 path
serves near-ties differently from float32 and reads a small gap; a token
from a wrong cache line, position or row reads the distance between a random
logit and the best of the vocabulary.
"""
from __future__ import annotations

import numpy as np


def pick_sample(finished: list[dict], n: int, seed: int) -> list[dict]:
    """The longest finished request and ``n - 1`` others drawn from the
    seed. Each entry holds ``tokens`` (prompt + served) and ``prompt_len``."""
    if not finished:
        return []
    order = sorted(range(len(finished)),
                   key=lambda i: (-len(finished[i]["tokens"]), i))
    chosen = [order[0]]
    rest = order[1:]
    rng = np.random.default_rng([int(seed), 0xC0FFEE])
    if rest and n > 1:
        take = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
        chosen += [rest[int(i)] for i in take]
    return [finished[i] for i in chosen]


def served_gaps(logits_at, w: dict, cfg: dict, sample: list[dict],
                quant: str | None = None) -> dict:
    """Gaps of the served tokens under the reference ``logits_at`` (the
    family's `reference.logits_at`); with ``quant`` the gaps of the tokens
    that the lower-precision pass puts first at the same positions of the
    same histories (the control)."""
    gaps: list[np.ndarray] = []
    for req in sample:
        toks, pl = req["tokens"], req["prompt_len"]
        where = list(range(pl - 1, len(toks) - 1))
        if not where:
            continue
        ref = logits_at(w, cfg, toks, where)
        if quant is None:
            picked = np.asarray(toks[pl:], np.int64)
        else:
            low = logits_at(w, cfg, toks, where, quant=quant)
            picked = low.argmax(axis=-1)
        best = ref.max(axis=-1)
        gaps.append(best - ref[np.arange(len(where)), picked])
    if not gaps:
        return {"tokens": 0, "requests": 0, "max_gap": float("nan"),
                "mean_gap": float("nan"), "agree": float("nan")}
    allg = np.concatenate(gaps)
    return {"tokens": int(allg.size), "requests": len(gaps),
            "max_gap": float(allg.max()), "mean_gap": float(allg.mean()),
            "agree": float((allg == 0.0).mean())}


def decide(numbers: dict[str, tuple[float, float]]) -> tuple[bool, dict]:
    """``numbers`` maps a short name to (value, limit); correct when every
    value is at or under its limit (a NaN is not)."""
    compared = {k: {"value": v, "limit": lim}
                for k, (v, lim) in numbers.items()}
    ok = all(v <= lim for v, lim in numbers.values())
    return bool(ok), compared

"""The system under test, built as `lm_serve` builds it (`serve/control.py`):
a `DecodeServer` from the configuration's serving parameters, driven by an
`LMServingLoop` and its own thread. What depends on the architecture (the
model, its parameters) comes from the configuration's family
(`benchmark/families/<family>/program.py`); beside those files this is the
only module of the benchmark that imports the program."""
from __future__ import annotations

import jax


def model_config(cfg: dict, rehearse: bool, family) -> dict:
    """The configuration as it is run; ``rehearse`` swaps in the tiny widths
    the file keeps for a CPU walk-through, and the family derives what
    follows from them."""
    if not rehearse:
        return cfg
    tiny = dict(cfg)
    r = dict(cfg["rehearse"])
    tiny["serving"] = dict(cfg["serving"], **r.pop("serving"))
    tiny["as_run"] = dict(cfg.get("as_run", {}), dtype=r.pop("dtype"))
    tiny.update(r)
    return family.program.derive(tiny)


def span_store(clock):
    """The program's own span store, on the benchmark's clock."""
    from idunno_tpu.utils.spans import SpanStore
    return SpanStore("bench", clock=clock, capacity=1 << 20)


def build(cfg: dict, w: dict, family, *, spans=None, name: str = "bench"):
    """(loop, server) over the configuration ``cfg`` and its family's
    weights ``w``."""
    from idunno_tpu.engine.serve_lm import DecodeServer
    from idunno_tpu.serve.lm_pool import LMServingLoop

    model, params, server_kwargs = family.program.build(cfg, w)
    s = cfg["serving"]
    server = DecodeServer(
        model, params, slots=int(s["slots"]),
        prompt_len=max(s["prompt_buckets"]), max_len=int(s["max_len"]),
        decode_steps=int(s["decode_steps"]),
        prompt_buckets=tuple(s["prompt_buckets"]),
        kv_block_size=int(s["kv_block_size"]),
        kv_cache_blocks=int(s["kv_cache_blocks"]),
        paged_kernel=s.get("paged_kernel"),
        prefill_chunk=int(s.get("prefill_chunk", 0)),
        n_model=int(s.get("n_model", 1)), **server_kwargs)
    loop = LMServingLoop(server, name=name, spans=spans)
    return loop, server


def require_chips(chips: int, rehearse: bool) -> dict:
    """The device as JAX reports it; raises unless an accelerator with at
    least ``chips`` devices is there (a rehearsal asks for the CPU)."""
    devs = jax.devices()
    plat = devs[0].platform
    if rehearse:
        if plat != "cpu":
            raise SystemExit("--rehearse runs on the CPU: set JAX_PLATFORMS=cpu")
    elif plat == "cpu" or len(devs) < chips:
        raise SystemExit(
            f"benchmark needs {chips} accelerator chip(s); JAX reports "
            f"{len(devs)} x {plat}")
    return {"platform": plat, "kind": devs[0].device_kind,
            "count": chips if not rehearse else len(devs)}


def memory_peak_bytes(chips: int) -> int:
    peak = 0
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak

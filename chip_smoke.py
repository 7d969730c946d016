#!/usr/bin/env python3
"""Standing on-chip check: the serving path, end to end, on one TPU.

    python chip_smoke.py            # one chip (what the driver runs)
    python chip_smoke.py --chips 4  # the sharded paths, one process, 4 chips
    python chip_smoke.py --rehearse # tiny sizes on the CPU; never "ok"

One chip. This process never imports JAX: a chip belongs to one process, and
that process is the node, started the way a deployment starts it
(`python -m idunno_tpu --host n0 --config <json> --no-shell`) and driven from
outside over the typed control RPC (`idunno_tpu/serve/control.py`):

  1. ResNet-18 `inference` query at full width (224² crop of 256² u8,
     `EngineConfig` defaults: batch 256, bf16, seeded random weights,
     synthetic seeded images) through coordinator → scheduler → worker →
     `InferenceEngine.infer` → results; count and determinism checked.
  2. `lm_serve` of a store-persisted LM at the TPU serving width
     (`utils/lm_bench.py:lm_bench_config`: dim 1024, depth 12, 16 heads,
     vocab 32768, bf16, 16 slots, max_len 512) with a paged KV block pool;
     prompts of different lengths, one sharing a prefix; greedy streams
     checked token-for-token against the node's own one-shot `generate`.
     The weights are seeded and saved by a helper child pinned to the CPU.
  3. The same again with `paged_kernel="pallas"`.
  4. After the node has exited, a second child takes the chip and compiles
     the Pallas kernels of the path (is `tpu_custom_call` in the compiled
     text?) and checks the paged kernel against its XLA twin.

Every earlier line is a JSON object worth keeping (phase wall times with the
first, compiling call apart from the second; JAX version; peak HBM; which
native library runs). The LAST line is
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}` and
the exit code 0 only if the device is a TPU and every phase passed; any
failure prints its reason and exits non-zero.
"""
from __future__ import annotations

import argparse
import json
import os
import random
import shutil
import socket
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))
SEED = 0

FULL = {
    "images": 600,
    "engine": {},                       # `EngineConfig` defaults
    # the width utils/lm_bench.py:lm_bench_config("tpu") serves
    "lm": {"dim": 1024, "depth": 12, "heads": 16, "vocab": 32768,
           "slots": 16, "prompt_len": 64, "max_len": 512, "block": 16,
           "max_new": 24, "dtype": "bfloat16"},
}
TINY = {
    "images": 20,
    "engine": {"batch_size": 8, "image_size": 64, "resize_size": 64},
    "lm": {"dim": 64, "depth": 2, "heads": 4, "vocab": 256,
           "slots": 4, "prompt_len": 32, "max_len": 64, "block": 8,
           "max_new": 6, "dtype": "float32"},
}


class SmokeFailure(Exception):
    pass


def say(**kw) -> None:
    print(json.dumps(kw), flush=True)


def check(cond: bool, why: str) -> None:
    if not cond:
        raise SmokeFailure(why)


def wait_for(probe, timeout_s: float, why: str, every: float = 0.05):
    """Poll ``probe`` until it returns something other than None."""
    deadline = time.time() + timeout_s
    while True:
        out = probe()
        if out is not None:
            return out
        check(time.time() < deadline, why)
        time.sleep(every)


def first_departures(got: list, want: list) -> list:
    """Per stream, the first index where the two token lists differ."""
    return [next((j for j, (x, y) in enumerate(zip(g, w)) if x != y), None)
            for g, w in zip(got, want)]


def lm_prompts(lm: dict) -> list[list[int]]:
    """Seeded prompts of different lengths; the last shares the first
    one's leading two blocks (a radix-cache hit) and then departs."""
    rng = random.Random(SEED)
    p, bs = lm["prompt_len"], lm["block"]

    def draw(n):
        return [rng.randrange(1, lm["vocab"]) for _ in range(n)]
    first = draw(p - bs // 2)
    return [first, draw(p), draw(bs + 3), first[:2 * bs] + draw(bs - 1)]


# ---------------------------------------------------------------------------
# children (the only code here that imports JAX)
# ---------------------------------------------------------------------------

def child_weights(size: dict, out: str) -> None:
    """Seeded LM → one `save_lm` blob in a file (the parent pins this
    child to the CPU)."""
    import jax
    import jax.numpy as jnp

    from idunno_tpu.engine.generate import save_lm
    from idunno_tpu.models.transformer import TransformerLM

    lm = size["lm"]
    dt = jnp.dtype(lm["dtype"])
    model = TransformerLM(vocab=lm["vocab"], dim=lm["dim"],
                          depth=lm["depth"], num_heads=lm["heads"],
                          causal=True, dtype=dt, param_dtype=dt)
    params = model.init(jax.random.PRNGKey(SEED),
                        jnp.zeros((1, 8), jnp.int32))["params"]

    class _FileStore:
        def put_bytes(self, name, blob):
            with open(out, "wb") as f:
                f.write(blob)
            return 1

    save_lm(_FileStore(), "smoke", model, params)


def child_kernels(size: dict, rehearse: bool) -> None:
    """Alone on the chip: compile the path's Pallas kernels at the smoke's
    shapes, look for the Mosaic custom call, and hold the paged kernel to
    its XLA twin on random pages."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from idunno_tpu.ops.flash_attention import flash_attention
    from idunno_tpu.ops.paged_attention import paged_attention_grouped
    from idunno_tpu.ops.pallas_preprocess import preprocess_batch_pallas
    from idunno_tpu.utils.compile_cache import enable_persistent_cache

    enable_persistent_cache()
    lm = size["lm"]
    interp = rehearse
    d = lm["dim"] // lm["heads"]
    bs, s = lm["block"], lm["slots"]
    c = lm["prompt_len"] // bs
    n = 4 * s * c
    report = {}

    def paged(kvh, int8):
        key = jax.random.PRNGKey(SEED)
        kq, kk, kv, kt, ks = jax.random.split(key, 5)
        q5 = jax.random.normal(kq, (s, 1, kvh, lm["heads"] // kvh, d),
                               jnp.float32)
        if int8:
            mk = lambda k: jax.random.randint(
                k, (n, bs, kvh, d), -127, 128, jnp.int8)
            scales = jax.random.uniform(ks, (2, n, bs, kvh), jnp.float32,
                                        0.001, 0.02)
            kw = {"k_scale_pages": scales[0], "v_scale_pages": scales[1]}
        else:
            mk = lambda k: jax.random.normal(
                k, (n, bs, kvh, d), jnp.float32).astype(lm["dtype"])
            kw = {}
        tables = jax.random.randint(kt, (s, c), 0, n, jnp.int32)
        lengths = (jnp.arange(s, dtype=jnp.int32) % (c + 1)) * bs
        args = (q5, mk(kk), mk(kv), tables, lengths)
        fn = jax.jit(lambda *a, **k: paged_attention_grouped(
            *a, **k, kernel="pallas", interpret=interp))
        text = fn.lower(*args, **kw).compile().as_text()
        o_p, lse_p = fn(*args, **kw)
        with jax.default_matmul_precision("highest"):
            o_x, lse_x = jax.jit(lambda *a, **k: paged_attention_grouped(
                *a, **k, kernel="xla"))(*args, **kw)
        err = float(jnp.max(jnp.abs(o_p - o_x)))
        live = np.asarray(lengths) > 0
        lerr = float(np.max(np.abs(np.asarray(lse_p)[live]
                                   - np.asarray(lse_x)[live])))
        return {"tpu_custom_call": "tpu_custom_call" in text,
                "max_abs_err_vs_xla": err, "max_lse_err_vs_xla": lerr,
                "finite": bool(jnp.isfinite(o_p).all())}

    for kvh in sorted({lm["heads"], max(1, lm["heads"] // 4), 1}):
        for int8 in (False, True):
            report[f"paged_kvh{kvh}_{'int8' if int8 else 'native'}"] = \
                paged(kvh, int8)

    # the two kernels other configurations of the same path select
    # (attn="flash" prefill, preprocess="pallas"): compiled, not driven
    b, t = (1, 128) if rehearse else (4, 1024)
    q = jnp.zeros((b, t, lm["heads"], d), lm["dtype"])
    text = jax.jit(lambda q: flash_attention(
        q, q, q, causal=True, interpret=interp)).lower(q).compile().as_text()
    report["flash_fwd"] = {"tpu_custom_call": "tpu_custom_call" in text}
    e = {"batch_size": 256, "resize_size": 256, "image_size": 224,
         **size["engine"]}
    u8 = jnp.zeros((e["batch_size"], e["resize_size"], e["resize_size"], 3),
                   jnp.uint8)
    text = preprocess_batch_pallas.lower(
        u8, crop=e["image_size"], interpret=interp).compile().as_text()
    report["pallas_preprocess"] = {
        "tpu_custom_call": "tpu_custom_call" in text}
    dev = jax.devices()[0]
    print(json.dumps({"phase": "kernels", "platform": dev.platform,
                      "kernels": report}), flush=True)
    bad = [k for k, v in report.items()
           if (not rehearse and not v["tpu_custom_call"])
           or v.get("max_abs_err_vs_xla", 0.0) > 5e-2
           or v.get("max_lse_err_vs_xla", 0.0) > 5e-2
           or not v.get("finite", True)]
    if bad:
        sys.exit(f"kernel checks failed: {bad}")


# ---------------------------------------------------------------------------
# the four-chip path: one process drives every device
# ---------------------------------------------------------------------------

def four_chips(size: dict, want: int, rehearse: bool) -> dict:
    """`--chips 4`: the two sharded serving paths against their one-device
    selves, and nothing else. ResNet-18 `InferenceEngine.infer` over the
    (4, 1) data mesh vs a one-device mesh, label for label; a
    `DecodeServer(n_model=2)` over the (2, 2) mesh (slots over "data",
    heads/hidden/vocab over "model") vs the unsharded pool, token for
    token. The LM comparison runs in float32 at `highest` matmul
    precision: the two pools then differ only in the order of f32 sums
    (the row-parallel psum), so equal tokens are what correct sharding
    must give. At the default precision the MXU rounds its inputs to
    bf16, a 1e-7 difference upstream can move such a rounding, and
    greedy streams part at near-ties without anything being wrong (first
    four-chip run of PR 22: 3 of 4 streams equal, one parted at its 10th
    new token inside a repetition loop)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from idunno_tpu.config import EngineConfig
    from idunno_tpu.engine.inference import InferenceEngine
    from idunno_tpu.engine.serve_lm import DecodeServer
    from idunno_tpu.models.transformer import TransformerLM
    from idunno_tpu.parallel.mesh import local_mesh, make_mesh
    from idunno_tpu.utils.compile_cache import enable_persistent_cache

    enable_persistent_cache()
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    say(phase="device", jax=jax.__version__, **device)
    check(len(devs) == want, f"--chips {want} but JAX sees {len(devs)}")
    if not rehearse:
        check(device["platform"] == "tpu",
              f"platform is {device['platform']!r}, not 'tpu'")

    def in_use():
        return [(d.memory_stats() or {}).get("bytes_in_use") for d in devs]

    def collectives(text):
        counts = {op: text.count(f" {op}(") + text.count(f" {op}-start(")
                  for op in ("all-reduce", "all-gather", "reduce-scatter",
                             "all-to-all", "collective-permute")}
        return {op: n for op, n in counts.items() if n}

    # -- ResNet-18 over the data mesh ------------------------------------
    ecfg = EngineConfig(**size["engine"])
    n = size["images"]
    out = {}
    for label, mesh in (("sharded", local_mesh()),
                        ("one_device", make_mesh(1, 1, devs[:1]))):
        eng = InferenceEngine(ecfg, mesh=mesh, seed=SEED, pretrained=False)
        t0 = time.time()
        eng.warmup("resnet18")
        t_warm = time.time() - t0
        mem = in_use()
        t0 = time.time()
        res = out[label] = eng.infer("resnet18", 0, n - 1)
        t_infer = time.time() - t0
        m = eng._models["resnet18"]
        bsz = eng._device_batch()
        text = m.predict.lower(
            m.variables, jax.ShapeDtypeStruct(
                (bsz, ecfg.resize_size, ecfg.resize_size, 3), jnp.uint8)
        ).compile().as_text()
        say(phase=f"resnet18_{label}", mesh=dict(mesh.shape),
            warmup_s=round(t_warm, 2), infer_s=round(t_infer, 2),
            records=len(res.records), bytes_in_use=mem,
            collectives=collectives(text))
        del eng, m
    a, b = out["sharded"].records, out["one_device"].records
    check(len(a) == len(b) == n, f"resnet18 records {len(a)}/{len(b)} != {n}")
    diff = [i for i in range(n) if a[i][:2] != b[i][:2]]
    check(not diff, f"resnet18 labels differ at {len(diff)} of {n} images "
                    f"(first {diff[:5]})")
    check(all(np.isfinite(r[2]) and abs(r[2] - q[2]) < 1e-2
              for r, q in zip(a, b)),
          "resnet18 probabilities differ between the meshes")

    # -- TP decode --------------------------------------------------------
    lm = size["lm"]
    model = TransformerLM(vocab=lm["vocab"], dim=lm["dim"],
                          depth=lm["depth"], num_heads=lm["heads"],
                          causal=True, dtype=jnp.float32,
                          param_dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(SEED),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    prompts = lm_prompts(lm)
    param_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    streams = {}

    def held(tree):
        """Bytes of a pytree's shards on each device, by device id."""
        per = dict.fromkeys((d.id for d in devs), 0)
        for leaf in jax.tree.leaves(tree):
            for sh in leaf.addressable_shards:
                per[sh.device.id] += sh.data.nbytes
        return [per[d.id] for d in devs]

    def serve(label, **kw):
        t0 = time.time()
        srv = DecodeServer(model, params, slots=lm["slots"],
                           prompt_len=lm["prompt_len"],
                           max_len=lm["max_len"],
                           kv_block_size=lm["block"], **kw)
        ids = [srv.submit(p, max_new=lm["max_new"]) for p in prompts]
        done = {c.id: c.tokens for c in srv.run_until_drained()}
        streams[label] = [done[i] for i in ids]
        text = srv._decode.lower(
            srv.params, srv._tokens, srv._cache, srv._cursors,
            srv._remaining, srv._temps, srv._top_ps, srv._top_ks,
            srv._keys, srv._logprobs, srv._pres, srv._freq,
            srv._counts).compile().as_text()
        p_held, kv_held = held(srv.params), held(srv._cache)
        cfg = srv.stats()["config"]
        say(phase=f"lm_{label}",
            mesh=dict(srv.mesh.shape) if srv.mesh is not None else None,
            wall_s=round(time.time() - t0, 2), n_model=cfg["n_model"],
            param_bytes=param_bytes, param_bytes_per_device=p_held,
            kv_bytes_per_device=kv_held, bytes_in_use=in_use(),
            decode_step_collectives=collectives(text))
        if label == "tp":
            check(all(0 < x < 0.75 * param_bytes for x in p_held),
                  f"TP weights are not spread: {p_held} of {param_bytes}")
            check(all(x == kv_held[0] and x > 0 for x in kv_held),
                  f"KV cache is not spread evenly: {kv_held}")
            check(collectives(text), "TP decode step has no collective")

    with jax.default_matmul_precision("highest"):
        serve("tp", mesh=local_mesh(n_model=2))
        serve("one_device")
    departs = first_departures(streams["tp"], streams["one_device"])
    check(all(d is None for d in departs),
          f"TP decode streams part from the one-device pool at tokens "
          f"{departs} (prompts of {[len(p) for p in prompts]} tokens)")
    say(phase="lm_tp_vs_one_device", prompts=len(prompts),
        tokens_compared=sum(len(x) for x in streams["tp"]), equal=True)
    return device


# ---------------------------------------------------------------------------
# the one-chip path: a node process, driven over its control RPC
# ---------------------------------------------------------------------------

def _free_port_base() -> int:
    for base in range(23000 + (os.getpid() * 7) % 2000, 64000, 777):
        try:
            for off in (0, 5):
                with socket.socket() as s:
                    s.bind(("127.0.0.1", base + off))
        except OSError:
            continue
        return base
    raise SmokeFailure("no free port range")


class NodeClient:
    def __init__(self, port: int, proc: subprocess.Popen, log: str):
        self.port, self.proc, self.log = port, proc, log

    def call(self, verb: str, timeout: float = 60.0, **kw) -> dict:
        from idunno_tpu.comm.message import Message
        from idunno_tpu.comm.net import oneshot_call
        from idunno_tpu.utils.types import MessageType
        out = oneshot_call("127.0.0.1", self.port, "control",
                           Message(MessageType.INFERENCE, "smoke",
                                   {"verb": verb, **kw}), timeout=timeout)
        check(out is not None, f"no reply to {verb}")
        check(out.type is MessageType.ACK, f"{verb}: {out.payload}")
        return out.payload

    def log_tail(self, n: int = 30) -> str:
        try:
            with open(self.log, errors="replace") as f:
                return "".join(f.readlines()[-n:])
        except OSError:
            return ""

    def wait_up(self, deadline_s: float) -> dict:
        from idunno_tpu.comm.transport import TransportError
        deadline = time.time() + deadline_s
        while True:
            if self.proc.poll() is not None:
                raise SmokeFailure(
                    f"node exited with code {self.proc.returncode} before "
                    f"serving:\n{self.log_tail()}")
            try:
                st = self.call("status", timeout=5.0)
                if st["members"].get("n0") == "RUNNING":
                    return st
            except (OSError, SmokeFailure, TransportError):
                pass                    # boot window: not serving yet
            check(time.time() < deadline,
                  f"node never answered:\n{self.log_tail()}")
            time.sleep(0.5)


def resnet_phase(node: NodeClient, size: dict) -> None:
    n = size["images"]
    rep = wait_for(lambda: node.call("status")["warmup"].get("resnet18"),
                   600, "resnet18 warm-up never finished", every=1.0)
    check(not isinstance(rep, str), f"resnet18 warm-up compile: {rep}")

    def query():
        t0 = time.time()
        qnums = node.call("inference", model="resnet18", start=0,
                          end=n - 1)["qnums"]
        records = []

        def finished(q):
            done = node.call("query_done", model="resnet18", qnum=q)
            check(not done["failed"], f"query {q} failed")
            return True if done["done"] else None
        for q in qnums:
            wait_for(lambda: finished(q), 600, f"query {q} never completed")
            res = node.call("results", model="resnet18", qnum=q)
            records += [tuple(r) for r in res["records"]]
        return records, time.time() - t0, res["weights"].get("resnet18")

    first, t1, weights = query()
    second, t2, _ = query()
    want = {f"test_{i}.JPEG" for i in range(n)}
    check(len(first) == n and {r[0] for r in first} == want,
          f"resnet18 returned {len(first)} records, want {n}")
    check(sorted(r[:2] for r in first) == sorted(r[:2] for r in second),
          "resnet18 labels differ between two runs of the same query")
    check(all(0.0 < r[2] <= 1.0 for r in first),
          "resnet18 probability outside (0, 1]")
    say(phase="resnet18", images=n, weights=weights,
        warmup_compile_s=round(rep, 2), query1_s=round(t1, 2),
        query2_s=round(t2, 2), distinct_labels=len({r[1] for r in first}))


def teacher_forced_agreement(node: NodeClient, lm: dict, streams: list,
                             lens: list) -> list:
    """Per stream, the share of the pool's generated tokens that `generate`
    picks too when it is handed the pool's own history: one ragged call,
    one row for each (stream, position), `max_new=1`. Greedy streams that
    run free part for good at the first near-tie — bf16 logits near 4 sit
    on a 1/32 grid, so at vocab 32768 exact ties are common and the pool
    (one T-token prefill) and `generate` (token by token) may break one
    differently. Forced onto the same history, a correct pool agrees
    everywhere but at such ties; a wrong cache, position or block table
    agrees nowhere."""
    width = lm["prompt_len"] + lm["max_new"]
    rows, row_lens, owner = [], [], []
    for i, (s, n) in enumerate(zip(streams, lens)):
        for k in range(n, len(s)):
            rows.append(s[:k] + [0] * (width - k))
            row_lens.append(k)
            owner.append((i, s[k]))
    out = node.call("generate", name="smoke", prompt=rows,
                    prompt_lens=row_lens, max_new=1, timeout=900.0)["tokens"]
    hits = [0] * len(streams)
    for row, k, (i, tok) in zip(out, row_lens, owner):
        hits[i] += row[k] == tok
    return [h / lm["max_new"] for h in hits]


def lm_phase(node: NodeClient, size: dict, pool: str, reference: list,
             **serve_kw) -> None:
    lm = size["lm"]
    prompts = lm_prompts(lm)
    t0 = time.time()
    out = node.call("lm_serve", name=pool, model="smoke",
                    slots=lm["slots"], prompt_len=lm["prompt_len"],
                    max_len=lm["max_len"], kv_block_size=lm["block"],
                    warmup=True, timeout=900.0, **serve_kw)
    check(out.get("slots") == lm["slots"], f"lm_serve: {out}")
    t_serve = time.time() - t0

    def run(batch):
        t0 = time.time()
        ids = [node.call("lm_submit", name=pool, prompt=p,
                         max_new=lm["max_new"])["id"] for p in batch]
        done = {}
        while len(done) < len(ids):
            reply = node.call("lm_poll", name=pool)
            check(not reply.get("errors"), f"{pool}: {reply.get('errors')}")
            for c in reply["completions"]:
                done[c["id"]] = c["tokens"]
            check(time.time() - t0 < 600, f"{pool}: requests never finished")
            time.sleep(0.02)
        return [done[i] for i in ids], time.time() - t0

    # the prefix-sharing prompt goes in once its donor's blocks are cached
    head, t1 = run(prompts[:-1])
    tail, t2 = run(prompts[-1:] + prompts[:1])
    got = head + tail
    want = reference + reference[:1]
    lens = [len(p) for p in prompts + prompts[:1]]
    for g, n in zip(got, lens):
        check(len(g) == n + lm["max_new"]
              and all(0 <= t < lm["vocab"] for t in g),
              f"{pool}: malformed stream of {len(g)} tokens")
    departs = first_departures(got, want)
    agree = teacher_forced_agreement(node, lm, got, lens)
    say(phase=pool + "_streams", free_running_equal_to_generate=[
            d is None for d in departs], first_departure=departs,
        teacher_forced_agreement=[round(a, 3) for a in agree])
    total = sum(agree) / len(agree)
    check(total >= 0.9 and min(agree) >= 0.75,
          f"{pool}: the pool's tokens are not generate's: next-token "
          f"agreement {agree} (streams depart at {departs})")
    stats = node.call("lm_stats", name=pool)["stats"]
    pc = stats.get("prefix_cache") or {}
    check(pc.get("hits", 0) >= 2, f"{pool}: no radix prefix hit: {pc}")
    if serve_kw.get("paged_kernel"):
        check(stats.get("kv_gather_bytes_saved", 0) > 0,
              f"{pool}: the paged path never served a hit")
    check(node.call("lm_stop", name=pool)["stopped"], f"{pool}: lm_stop")
    say(phase=pool, serve_and_compile_s=round(t_serve, 2),
        first_batch_s=round(t1, 2), second_batch_s=round(t2, 2),
        tokens_checked=len(got) * lm["max_new"],
        streams_equal_to_generate=f"{departs.count(None)}/{len(got)}",
        next_token_agreement=round(total, 4),
        prefix_hits=pc.get("hits"),
        cached_tokens_saved=pc.get("cached_tokens_saved"),
        kv_gather_bytes_saved=stats.get("kv_gather_bytes_saved"),
        paged_kernel=stats.get("config", {}).get("paged_kernel"))


def generate_reference(node: NodeClient, size: dict) -> list:
    """The one-shot `generate` verb on the same node, same weights: one
    ragged call (right-padded prompts + prompt_lens), timed twice."""
    lm = size["lm"]
    prompts = lm_prompts(lm)
    width = lm["prompt_len"]
    padded = [p + [0] * (width - len(p)) for p in prompts]
    lens = [len(p) for p in prompts]
    walls = []
    for _ in range(2):
        t0 = time.time()
        out = node.call("generate", name="smoke", prompt=padded,
                        prompt_lens=lens, max_new=lm["max_new"],
                        timeout=900.0)["tokens"]
        walls.append(time.time() - t0)
    ref = [row[:n + lm["max_new"]] for row, n in zip(out, lens)]
    for p, r in zip(prompts, ref):
        check(r[:len(p)] == p, "generate did not echo its prompt")
        check(all(0 <= t < lm["vocab"] for t in r), "token outside vocab")
    say(phase="generate", first_call_s=round(walls[0], 2),
        second_call_s=round(walls[1], 2), prompts=lens,
        max_new=lm["max_new"])
    return ref


def one_chip(size: dict, rehearse: bool) -> dict:
    env = dict(os.environ)
    if rehearse:
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    cpu_env = {**env, "JAX_PLATFORMS": "cpu"}
    me = [sys.executable, os.path.abspath(__file__)]
    flags = ["--rehearse"] if rehearse else []
    work = tempfile.mkdtemp(prefix="chip_smoke_")
    node = weights = None
    try:
        base = _free_port_base()
        cfg = {"hosts": ["n0"], "coordinator": "n0",
               "standby_coordinator": "n0", "introducer": "n0",
               "ports": {"membership": base, "store": base + 5,
                         "inference": base + 10, "result": base + 15,
                         "metadata": base + 20, "grep": base + 25},
               "replication_factor": 1, "query_interval_s": 0.0,
               "engine": {**size["engine"], "warmup_models": ["resnet18"]}}
        cfg_path = os.path.join(work, "cluster.json")
        with open(cfg_path, "w") as f:
            json.dump(cfg, f)
        blob = os.path.join(work, "lm.blob")
        # the weights child is pinned to the CPU, so it may run while the
        # node (the one process that opens the chip) boots and warms up
        weights = subprocess.Popen(
            me + ["--child", "weights", "--out", blob] + flags,
            cwd=REPO, env=cpu_env)
        log = os.path.join(work, "node.log")
        t0 = time.time()
        with open(log, "w") as lf:
            proc = subprocess.Popen(
                [sys.executable, "-m", "idunno_tpu", "--host", "n0",
                 "--config", cfg_path, "--no-shell",
                 "--data-dir", os.path.join(work, "n0")]
                + (["--cpu"] if rehearse else []),
                cwd=REPO, env=env, stdout=lf, stderr=subprocess.STDOUT)
        node = NodeClient(base + 5, proc, log)
        node.wait_up(300)
        dev = node.call("device")
        device = {k: dev[k] for k in ("platform", "kind", "count")}
        say(phase="node_up", boot_s=round(time.time() - t0, 2),
            jax=dev["jax"], native=dev["native"],
            compile_cache_dir=dev["compile_cache"]["dir"], **device)
        if not rehearse:
            check(device["platform"] == "tpu",
                  f"platform is {device['platform']!r}, not 'tpu'")

        resnet_phase(node, size)

        check(weights.wait(timeout=600) == 0, "the weights child failed")
        t0 = time.time()
        node.call("put", local=blob, name="lm/smoke", timeout=300.0)
        say(phase="lm_weights_stored", bytes=os.path.getsize(blob),
            put_s=round(time.time() - t0, 2))
        reference = generate_reference(node, size)
        lm_phase(node, size, "lm_gathered", reference)
        lm_phase(node, size, "lm_paged_pallas", reference,
                 paged_kernel="pallas")

        dev = node.call("device")
        st = node.call("status")
        check(not any(isinstance(v, str) for v in st["warmup"].values()),
              f"warm-up errors: {st['warmup']}")
        peak = [(m or {}).get("peak_bytes_in_use")
                for m in dev["memory_stats"]]
        say(phase="node_totals", peak_bytes_in_use=peak,
            compile_cache=dev["compile_cache"], models=st["models"])
    except SmokeFailure:
        raise
    except Exception as e:  # noqa: BLE001 - every failure names the node log
        raise SmokeFailure(
            f"{type(e).__name__}: {e}\n"
            f"{node.log_tail() if node else ''}") from e
    finally:
        for p in (weights, node.proc if node else None):
            if p is not None and p.poll() is None:
                p.terminate()
                try:
                    p.wait(timeout=20)
                except subprocess.TimeoutExpired:
                    p.kill()
                    p.wait()
        shutil.rmtree(work, ignore_errors=True)

    # the node is gone and the chip is free: one more child may take it
    rc = subprocess.run(me + ["--child", "kernels"] + flags, cwd=REPO,
                        env=env, timeout=900).returncode
    check(rc == 0, f"the kernel child exited with code {rc}")
    return device


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 = only the sharded paths vs their one-device "
                         "selves, one process driving four chips")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU (interpret-mode kernels); "
                         "proves control flow, never prints an ok line")
    ap.add_argument("--child", choices=("weights", "kernels"),
                    help=argparse.SUPPRESS)
    ap.add_argument("--out", help=argparse.SUPPRESS)
    args = ap.parse_args()
    size = TINY if args.rehearse else FULL
    sys.path.insert(0, REPO)
    if args.child == "weights":
        child_weights(size, args.out)
        return 0
    if args.child == "kernels":
        child_kernels(size, args.rehearse)
        return 0
    try:
        import idunno_tpu  # noqa: F401 - the program under test
    except ImportError as e:
        print(f"chip_smoke: the program is not here: {e}", flush=True)
        return 2
    try:
        if args.chips == 4:
            if args.rehearse:
                os.environ["JAX_PLATFORMS"] = "cpu"
                os.environ["XLA_FLAGS"] = (
                    "--xla_force_host_platform_device_count=4")
            device = four_chips(size, args.chips, args.rehearse)
        else:
            device = one_chip(size, args.rehearse)
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", flush=True)
        return 1
    if args.rehearse or device["platform"] != "tpu":
        print(f"chip_smoke: every phase passed, but on "
              f"{device['platform']!r}: a rehearsal, not a chip run",
              flush=True)
        return 3
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

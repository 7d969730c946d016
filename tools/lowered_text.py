"""Which programs did a change touch? From the root of a checkout, on the
CPU: lower the decode dispatch (`jit_run`), `_prefill` and `_prefill_chunk`
of a StarCoder2-shaped toy pool and of every hybrid family's rehearsal
stack, and print a hash of each one's StableHLO text. Run it in a copy of
the parent commit and in the change, and compare the lines:

    JAX_PLATFORMS=cpu python tools/lowered_text.py > change.txt
    (cd <parent copy> &&
     JAX_PLATFORMS=cpu python <here>/tools/lowered_text.py) > parent.txt
    diff parent.txt change.txt

A line that is equal means the program is the parent's text for text: the
chip's compiler is handed the same thing. Nothing is compiled or run."""
import hashlib
import os
import sys

sys.path.insert(0, os.getcwd())

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmark import manifest, system  # noqa: E402
from idunno_tpu.engine import serve_lm  # noqa: E402
from idunno_tpu.engine.generate import init_cache  # noqa: E402
from idunno_tpu.models.transformer import TransformerLM  # noqa: E402

HYBRID_CELLS = ("minicpm-sala.long-doc", "granite-4.0-h-small.chat",
                "falcon-h1-34b-instruct.reasoning")


def programs(name: str, srv: serve_lm.DecodeServer, chunk: int) -> None:
    def show(program, lowered):
        text = lowered.as_text().encode()
        print(name, program, hashlib.sha256(text).hexdigest()[:16])

    state = (srv._tokens, srv._cache, srv._cursors, srv._remaining,
             srv._temps, srv._top_ps, srv._top_ks, srv._keys, srv._logprobs,
             srv._pres, srv._freq, srv._counts)
    run = srv._build_decode(srv.decode_steps).__wrapped__
    show("jit_run", jax.jit(
        run, donate_argnums=serve_lm._DECODE_DONATED).lower(
        srv.params, *state))
    pl = srv.prompt_buckets[-1]
    tok = jnp.zeros((1, pl), jnp.int32)
    model = srv._prefill_model
    show("_prefill", serve_lm._prefill.lower(
        model, srv.params, tok, jnp.int32(pl - 3), pl))
    show("_prefill_chunk", serve_lm._prefill_chunk.lower(
        model, srv.params, init_cache(model, 1, pl), tok[:, :chunk],
        jnp.int32(chunk), pl, None, None, None))


def main() -> None:
    dt = jnp.bfloat16
    toy = TransformerLM(vocab=64, dim=24 * 8, depth=2, num_heads=24,
                        num_kv_heads=2, dtype=dt, param_dtype=dt)
    params = toy.init(jax.random.PRNGKey(0),
                      jnp.zeros((1, 8), jnp.int32))["params"]
    programs("starcoder2-shaped", serve_lm.DecodeServer(
        toy, params, slots=4, prompt_len=1024, max_len=4096, decode_steps=4,
        prompt_buckets=(64, 1024), kv_block_size=16, kv_cache_blocks=64,
        prefill_chunk=256), 256)
    man = manifest.Manifest()
    for cell in HYBRID_CELLS:
        cfg = man.config(man.cell(cell))
        fam = man.family(cfg)
        toy = system.model_config(cfg, True, fam)
        model, params, kw = fam.program.build(
            toy, fam.weights.make_weights(toy, 1))
        programs(cell, serve_lm.DecodeServer(
            model, params, slots=4, prompt_len=512, max_len=1024,
            decode_steps=4, prompt_buckets=(128, 512), kv_block_size=64,
            kv_cache_blocks=8, prefill_chunk=128, **kw), 128)


if __name__ == "__main__":
    main()

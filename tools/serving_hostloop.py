"""Measure the batched-serving scheduler's HOST cost per tick.

Round-3's verdict (weak #5) flagged ``BatchedGenerator.step`` as a
potential host-side bottleneck — per-token Python under a lock with numpy
marshalling for all slots — and noted it was unmeasured.  This tool
separates the host loop from device compute on the CPU backend (where the
tiny model's dispatch is cheap and timing is honest):

  raw dispatch   the ragged sampled_steps program alone, B = n_slots
  generator      BatchedGenerator.step() with all slots busy on long
                 prompts (admission excluded)

host overhead per tick = generator ms - raw ms.  The budget it must fit
inside on TPU is the weight-streaming time of a real model (e.g. ~29 ms
for the 8B shape), times --decode-chunk when chunked ticks amortize it.

Usage: python tools/serving_hostloop.py [n_slots] [ticks]
"""

from __future__ import annotations

import os
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tests"))

# host-loop cost is a CPU-side question; force the cpu backend. Override
# with DLLAMA_HOSTLOOP_PLATFORM to measure on the real chip (this process is
# then the one that holds it: the script starts no child).
_platform = os.environ.get("DLLAMA_HOSTLOOP_PLATFORM", "cpu")
os.environ["JAX_PLATFORMS"] = _platform


SEQ_LEN = 256
PROMPT_LEN = 28


def main() -> None:
    n_slots = int(sys.argv[1]) if len(sys.argv) > 1 else 16
    ticks = int(sys.argv[2]) if len(sys.argv) > 2 else 200
    # a slot retires at the seq_len cap and ticks on an empty pool cost ~0,
    # which would silently deflate the measured host cost — cap instead
    max_ticks = SEQ_LEN - PROMPT_LEN - 4
    if ticks > max_ticks:
        print(f"capping ticks {ticks} -> {max_ticks} (seq_len budget)")
        ticks = max_ticks

    import jax

    jax.config.update("jax_platforms", _platform)
    import numpy as np

    from helpers import byte_vocab_tokenizer, tiny_header_params, \
        write_tiny_model
    from dllama_tpu.formats import tfile
    from dllama_tpu.runtime.engine import InferenceEngine
    from dllama_tpu.runtime.serving import BatchedGenerator, Request

    d = tempfile.mkdtemp()
    m, t = os.path.join(d, "m.m"), os.path.join(d, "t.t")
    rng = np.random.default_rng(5)
    write_tiny_model(m, tiny_header_params(vocab_size=268, seq_len=SEQ_LEN),
                     rng)
    tfile.write_tfile(t, byte_vocab_tokenizer())

    eng = InferenceEngine(m, t, temperature=0.8, topp=0.9, seed=11)
    gen = BatchedGenerator(eng, n_slots=n_slots)

    for i in range(n_slots):
        r = Request(rid=i, prompt_ids=list(range(2, 2 + PROMPT_LEN)),
                    max_tokens=10 ** 6, temperature=0.8, topp=0.9,
                    seed=100 + i)
        gen.admit(r, i)

    gen.step()  # compile + first ragged dispatch
    t0 = time.perf_counter()
    for _ in range(ticks):
        gen.step()
    dt = time.perf_counter() - t0
    gen_ms = 1e3 * dt / ticks

    # raw program: the same ragged sampled step the generator dispatches,
    # without the scheduler around it
    import jax.numpy as jnp

    from dllama_tpu.models.llama import sampled_step

    kv = gen.kv
    tok = jnp.ones((n_slots,), jnp.int32)
    pos = jnp.asarray(np.full((n_slots,), 40, np.int32))
    temps = jnp.full((n_slots,), 0.8, jnp.float32)
    topps = jnp.full((n_slots,), 0.9, jnp.float32)
    coins = jnp.full((n_slots,), 0.5, jnp.float32)
    step = jax.jit(sampled_step, static_argnums=1)
    tokn, kv = step(eng.params, gen.cfg, tok[:, None], pos, kv, temps,
                    topps, coins)
    jax.block_until_ready(tokn)
    t0 = time.perf_counter()
    for i in range(ticks):
        tokn, kv = step(eng.params, gen.cfg, tok[:, None], pos, kv, temps,
                        topps, coins)
    jax.block_until_ready(tokn)
    raw_ms = 1e3 * (time.perf_counter() - t0) / ticks

    print(f"slots={n_slots} ticks={ticks}")
    print(f"generator.step(): {gen_ms:.2f} ms/tick "
          f"({n_slots * 1e3 / gen_ms:.0f} tok/s aggregate)")
    print(f"raw ragged dispatch: {raw_ms:.2f} ms/tick")
    print(f"host overhead: {gen_ms - raw_ms:.2f} ms/tick "
          f"({100 * (gen_ms - raw_ms) / gen_ms:.0f}% of tick)")


if __name__ == "__main__":
    main()

"""Batched serving demo: decode a batch of requests with the KV/state
cache for three different cache families (dense GQA ring-buffer window,
SSM constant-state, MLA compressed) — the per-request loop is the shared
``repro.launch.serve.greedy_decode`` helper, then the same workload runs
through the slot-based continuous-batching engine
(``repro.serve.decode``): one pre-allocated cache pool, per-step
admission into freed slots, byte-identical tokens.

  PYTHONPATH=src python examples/serve_batched.py
"""

import time

import jax
import numpy as np

from repro.configs.base import get_config
from repro.core.spec import DecodeSpec
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.serve import cache_nbytes, greedy_decode
from repro.models import model as M
from repro.serve.decode import DecodeEngine, DecodeRequest


def serve(arch: str, batch=4, prompt_len=16, gen=16):
    cfg = get_config(arch).reduced()
    params = M.init_params(cfg, jax.random.key(0))
    prompt = jax.random.randint(jax.random.key(1), (batch, prompt_len), 0,
                                cfg.vocab_size)
    # baseline: one request at a time through the B=1 greedy helper — the
    # engine's byte-determinism contract is against exactly this loop
    t0 = time.perf_counter()
    gen_toks = np.concatenate([
        np.asarray(jax.device_get(greedy_decode(cfg, params, row[None, :],
                                                gen)))
        for row in prompt])
    dt = time.perf_counter() - t0
    cache_bytes = cache_nbytes(cfg, batch, prompt_len + gen)
    print(f"{arch:22s} cache={cache_bytes/1e6:6.2f}MB "
          f"{batch * gen / dt:6.1f} tok/s  first: {gen_toks[0, :8].tolist()}")

    # the same requests through the continuous-batching slot pool: mixed
    # generation lengths, one shared cache block, tokens byte-identical
    # to the per-request loop (and to their solo replay)
    eng = DecodeEngine(cfg, params,
                       DecodeSpec(slots=batch, max_seq=prompt_len + gen))
    prompts = np.asarray(jax.device_get(prompt))
    t0 = time.perf_counter()
    futs = [eng.submit(DecodeRequest(user_id=i, prompt=p, max_new=gen))
            for i, p in enumerate(prompts)]
    eng.drain()
    dt = time.perf_counter() - t0
    pooled = np.stack([f.result() for f in futs])
    match = np.array_equal(pooled, np.asarray(gen_toks))
    st = eng.engine_stats()
    print(f"{'':22s} pool ={eng.pool_nbytes/1e6:6.2f}MB "
          f"{batch * gen / dt:6.1f} tok/s  programs={st['programs']} "
          f"bytes_match_greedy={match}")
    assert match, "continuous batching changed the bytes"


def main():
    enable_compile_cache()
    for arch in ["tinyllama-1.1b", "mamba2-780m", "deepseek-v2-lite-16b"]:
        serve(arch)


if __name__ == "__main__":
    main()

"""Serving launcher: batched greedy decode against the KV/state cache.

``greedy_decode`` is the simple per-request serving loop — the CLI below
and ``examples/serve_batched.py`` both drive it (the loop used to be
copy-pasted between the two); ``--continuous`` runs the same workload
through the slot-based continuous-batching engine
(``repro.serve.decode``), which shares one pre-allocated cache pool
across requests instead of allocating per call.  ``cache_nbytes`` is
re-exported from its canonical home in ``repro.models.cache`` (it moved
there so the slot-pool code prices its block with the same function).

  PYTHONPATH=src python -m repro.launch.serve --arch mamba2-780m --reduced \
      --batch 4 --prompt-len 32 --gen 32 [--continuous --slots 8]
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs.base import get_config
from repro.data.synthetic import synthetic_batch_for
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as M
from repro.models.cache import cache_nbytes  # noqa: F401  (re-export)


def greedy_decode(cfg, params, prompt, gen_len: int, *, src_embeds=None):
    """prompt: (B, S0) -> generated (B, gen_len).  Prefill is token-by-token
    decode here (simple and uniform across SSM/attention archs)."""
    B, S0 = prompt.shape
    cache = M.init_cache(cfg, B, S0 + gen_len)
    if cfg.arch_type == "audio":
        assert src_embeds is not None
        cache = M.prefill_audio_cache(params, cache, src_embeds, cfg)

    step = jax.jit(
        lambda p, c, t, i: M.decode_step(p, c, t, i, cfg))

    tok = prompt[:, 0:1]
    out = []
    for i in range(S0 + gen_len - 1):
        logits, cache = step(params, cache, tok, jnp.int32(i))
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
        tok = prompt[:, i + 1:i + 2] if i + 1 < S0 else nxt
        if i + 1 >= S0:
            out.append(nxt)
    return jnp.concatenate(out, axis=1)


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--continuous", action="store_true",
                    help="serve through the slot-based continuous-"
                         "batching engine instead of per-request greedy")
    ap.add_argument("--slots", type=int, default=8,
                    help="decode-slot pool width (with --continuous)")
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    params = M.init_params(cfg, jax.random.key(args.seed))
    batch = synthetic_batch_for(cfg, args.batch, args.prompt_len,
                                jax.random.key(args.seed + 1))

    if args.continuous:
        from repro.core.spec import DecodeSpec
        from repro.serve.decode import DecodeEngine, DecodeRequest

        spec = DecodeSpec(slots=args.slots,
                          max_seq=args.prompt_len + args.gen)
        eng = DecodeEngine(cfg, params, spec)
        print(f"[serve] slot pool: {spec.slots} x {spec.max_seq} = "
              f"{eng.pool_nbytes / 1e6:.2f} MB shared cache block")
        prompts = jax.device_get(batch["tokens"])
        t0 = time.perf_counter()
        futs = [eng.submit(DecodeRequest(user_id=i, prompt=p,
                                         max_new=args.gen))
                for i, p in enumerate(prompts)]
        eng.drain()
        gen = jnp.stack([jnp.asarray(f.result()) for f in futs])
        dt = time.perf_counter() - t0
        st = eng.engine_stats()
        print(f"[serve] {cfg.name}: generated {gen.shape} in {dt:.1f}s "
              f"({args.batch * args.gen / dt:.1f} tok/s incl. compile); "
              f"programs {st['programs']}, "
              f"mean occupancy {st.get('mean_occupancy', 0):.1f}")
    else:
        t0 = time.perf_counter()
        gen = greedy_decode(cfg, params, batch["tokens"], args.gen,
                            src_embeds=batch.get("src_embeds"))
        gen = jax.device_get(gen)
        dt = time.perf_counter() - t0
        print(f"[serve] {cfg.name}: generated {gen.shape} in {dt:.1f}s "
              f"({args.batch * args.gen / dt:.1f} tok/s incl. compile)")
    print("[serve] first row:", jax.device_get(gen)[0, :16].tolist())


if __name__ == "__main__":
    main()

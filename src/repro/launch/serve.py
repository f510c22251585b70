"""Serving launcher CLI.

One-shot static batching (LM/VLM families):

  PYTHONPATH=src python -m repro.launch.serve --arch gemma3-27b-smoke
      --batch 4 --prompt-len 16 --new 32 [--temperature 0.7]

Continuous batching (LM families and the paper's RNN-T CRDNN):

  PYTHONPATH=src python -m repro.launch.serve --arch starcoder2-3b-smoke
      --engine slots --requests 16 --n-slots 4 --new 32
  PYTHONPATH=src python -m repro.launch.serve --arch rnnt-crdnn-smoke
      --engine slots --requests 8 --prompt-len 48
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs import get_config
from repro.launch.cache import enable_compile_cache
from repro.models.api import build_model
from repro.serve.engine import Request, SlotEngine, generate


def _oneshot(args, cfg, bundle, params, key):
    prompts = jax.random.randint(
        jax.random.fold_in(key, 1), (args.batch, args.prompt_len), 0,
        cfg.vocab_size)
    extra = {}
    if cfg.family == "vlm":
        extra["patches"] = jax.random.normal(
            jax.random.fold_in(key, 2),
            (args.batch, cfg.n_prefix, cfg.d_model))
    toks, stats = generate(bundle, params, prompts, args.new,
                           temperature=args.temperature, key=key,
                           extra_inputs=extra)
    print(f"{cfg.name}: {toks.shape} tokens — prefill "
          f"{stats.prefill_s*1e3:.1f} ms "
          f"({stats.prompt_tokens}+{stats.prefill_tokens} tok), decode "
          f"{stats.decode_s*1e3:.1f} ms / {stats.decode_steps} steps "
          f"({stats.decode_tokens} live tok, {stats.tokens_per_s:.1f} tok/s)")


def _slots(args, cfg, bundle, params, key):
    rng = np.random.default_rng(args.seed)
    reqs = []
    for i in range(args.requests):
        L = int(rng.integers(args.prompt_len // 2, args.prompt_len + 1))
        if cfg.family == "rnnt":
            inputs = {"feats": rng.normal(
                size=(L, cfg.rnnt.n_feats)).astype(np.float32)}
        else:
            inputs = {"tokens": rng.integers(
                0, cfg.vocab_size, (L,)).astype(np.int32)}
        reqs.append(Request(uid=i, inputs=inputs, max_new_tokens=args.new))
    eng = SlotEngine(bundle, params, n_slots=args.n_slots,
                     max_new_tokens=args.new,
                     max_prompt_len=args.prompt_len,
                     temperature=args.temperature, eos_id=args.eos_id,
                     sync_every=args.sync_every, seed=args.seed)
    import time
    t0 = time.time()
    comps = eng.run(reqs)
    wall = time.time() - t0
    lat = sorted(c.latency_s for c in comps)
    n_tok = sum(len(c.tokens) for c in comps)
    print(f"{cfg.name}: {len(comps)} requests / {eng.n_slots} slots — "
          f"{wall*1e3:.0f} ms wall, {len(comps)/wall:.1f} req/s, "
          f"{n_tok} tokens, p50 latency {lat[len(lat)//2]*1e3:.0f} ms, "
          f"{eng.n_decode_dispatches} decode dispatches")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--engine", choices=("oneshot", "slots"),
                    default="oneshot")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--n-slots", type=int, default=4)
    ap.add_argument("--sync-every", type=int, default=4)
    ap.add_argument("--eos-id", type=int, default=None)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    bundle = build_model(cfg)
    key = jax.random.PRNGKey(args.seed)
    params = bundle.init_params(key)
    if args.engine == "slots" or cfg.family == "rnnt":
        _slots(args, cfg, bundle, params, key)
    else:
        _oneshot(args, cfg, bundle, params, key)


if __name__ == "__main__":
    main()

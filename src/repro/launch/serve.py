"""Batched-decoding server demo: prefill a prompt batch, then decode
tokens with the KV-cache serve step.

    PYTHONPATH=src python -m repro.launch.serve --arch starcoder2-3b \
        --reduced --batch 4 --prompt-len 32 --gen 32
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.launch.cache import enable_compilation_cache
from repro.models import api


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="starcoder2-3b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    enable_compilation_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    params = api.init_params(jax.random.key(args.seed), cfg)
    rng = np.random.default_rng(args.seed)
    s_max = args.prompt_len + args.gen

    prompts = jnp.asarray(
        rng.integers(0, cfg.vocab, (args.batch, args.prompt_len)), jnp.int32)
    cache = api.init_cache(cfg, args.batch, s_max)

    decode = jax.jit(lambda p, c, t, pos: api.decode_step(p, cfg, c, t, pos))

    # prefill by teacher-forcing the prompt through the decode step (keeps
    # one compiled program; a production server would batch-prefill).
    t0 = time.perf_counter()
    logits = None
    for t in range(args.prompt_len):
        logits, cache = decode(params, cache, prompts[:, t:t + 1],
                               jnp.full((args.batch,), t, jnp.int32))
    toks = jnp.argmax(logits, -1)[:, None]
    out = [toks]
    for t in range(args.prompt_len, s_max):
        logits, cache = decode(params, cache, toks,
                               jnp.full((args.batch,), t, jnp.int32))
        toks = jnp.argmax(logits, -1)[:, None]
        out.append(toks)
    jax.block_until_ready(toks)
    dt = time.perf_counter() - t0
    gen = jnp.concatenate(out, axis=1)
    total_tokens = args.batch * s_max
    print(f"{args.arch}: served {args.batch} seqs x ({args.prompt_len} prompt "
          f"+ {args.gen} generated) = {total_tokens} steps in {dt:.2f}s "
          f"({total_tokens / dt:.1f} tok/s)")
    print("sample generations (token ids):")
    for b in range(min(2, args.batch)):
        print(f"  seq{b}: {np.asarray(gen[b, :16])}")


if __name__ == "__main__":
    main()

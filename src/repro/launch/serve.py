"""Batched greedy serving driver (prefill via decode loop + token generation).

Demonstrates the decode path end-to-end on CPU with reduced configs:
  PYTHONPATH=src python -m repro.launch.serve --arch gemma2-2b --smoke \
      --batch 4 --prompt-len 12 --gen 16
"""
from __future__ import annotations

import argparse
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs.registry import build_model
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.train import parse_mesh


def generate(model, params, prompts: jax.Array, gen: int, max_len: int):
    """Greedy decode: feed prompt tokens, then sample `gen` new ones."""
    B, Lp = prompts.shape
    cache = model.init_cache(B, max_len)
    if model.cfg.family == "encdec":
        raise NotImplementedError("use prefill_cross + decode for enc-dec")
    step = jax.jit(model.decode_step)

    tok = prompts[:, :1]
    out = [tok]
    for t in range(Lp + gen - 1):
        logits, cache = step(params, cache, tok, jnp.full((B,), t, jnp.int32))
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
        tok = prompts[:, t + 1 : t + 2] if t + 1 < Lp else nxt
        out.append(tok)
    return jnp.concatenate(out, axis=1)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma-2b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--mesh", default="1x1")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    mesh = parse_mesh(args.mesh)
    model = build_model(args.arch, mesh if mesh.size > 1 else None, smoke=args.smoke)
    with mesh:
        params = model.init_params(args.seed)
        prompts = jax.random.randint(
            jax.random.PRNGKey(args.seed), (args.batch, args.prompt_len),
            0, model.cfg.vocab)
        t0 = time.perf_counter()
        seqs = generate(model, params, prompts, args.gen, args.prompt_len + args.gen)
        dt = time.perf_counter() - t0
        n_new = args.batch * args.gen
        print(f"generated {n_new} tokens in {dt:.2f}s "
              f"({n_new/dt:.1f} tok/s incl. prefill+compile)")
        print("sample:", np.asarray(seqs[0]).tolist())
    return np.asarray(seqs)


if __name__ == "__main__":
    enable_compile_cache()
    main()

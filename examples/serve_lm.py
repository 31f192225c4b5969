"""End-to-end driver #3: serve a small LM with batched requests — prefill
(teacher-forced) + batched greedy decode against ring-buffer KV caches.

  PYTHONPATH=src python examples/serve_lm.py --arch gemma3-4b --batch 4
"""
import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_smoke_config
from repro.data import SyntheticTokens
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.launch.steps import generate, make_ctx
from repro.models import LM


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-4b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=24)
    ap.add_argument("--gen", type=int, default=12)
    args = ap.parse_args()
    enable_compile_cache(Path(__file__).resolve().parents[1])

    cfg = get_smoke_config(args.arch)
    lm = LM(cfg)
    ctx = make_ctx(make_local_mesh(), seq_sharded=False)
    params, _ = lm.init(jax.random.key(0))
    prompts = jnp.asarray(
        SyntheticTokens(cfg.vocab, args.prompt_len, args.batch).batch(0))
    t0 = time.time()
    toks = generate(lm, params, ctx, prompts, args.gen)
    dt = time.time() - t0
    print(f"[serve_lm] {args.arch}: batch={args.batch} "
          f"prompt={args.prompt_len} gen={args.gen} -> {toks.shape} "
          f"in {dt:.2f}s ({args.batch*args.gen/dt:.1f} tok/s)")
    print(np.asarray(toks))


if __name__ == "__main__":
    main()

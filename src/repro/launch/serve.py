"""Serving launcher: batched decode with the continuous-batching engine.

``python -m repro.launch.serve --arch qwen2.5-3b --reduced --requests 8``
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from ..compile_cache import enable_compile_cache
from ..configs import registry
from ..models import Model
from ..serve.engine import ServeEngine


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--max-new", type=int, default=16)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = registry.get_reduced(args.arch) if args.reduced \
        else registry.get(args.arch)
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    eng = ServeEngine(model, params, slots=args.slots, max_seq=args.max_seq)

    rng = np.random.default_rng(0)
    t0 = time.perf_counter()
    for _ in range(args.requests):
        plen = int(rng.integers(4, 16))
        eng.submit(rng.integers(0, cfg.vocab, plen).astype(np.int32),
                   max_new_tokens=args.max_new)
    done = eng.run_to_completion()
    dt = time.perf_counter() - t0
    toks = sum(len(r.out_tokens) for r in done)
    print(f"{len(done)} requests, {toks} tokens in {dt:.2f}s "
          f"({toks/dt:.1f} tok/s aggregate)")
    for r in done[:3]:
        print(f"  req {r.rid}: {r.out_tokens[:8]}...")
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())

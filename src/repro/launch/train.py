"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

On this CPU container you run the reduced configs (smoke scale); on a real
TPU slice the same entry point takes the full configs and the production
mesh.  Data comes from a columnar TokenStore (synthesized on the fly if the
path is empty), checkpoints/metrics go into columnar stores.
"""
from __future__ import annotations

import argparse
import os

import jax
import numpy as np

from ..compile_cache import enable_compile_cache
from ..configs import registry
from ..data.sharded_loader import ShardedLoader
from ..data.tokenstore import TokenStore
from ..models import Model
from ..train.optimizer import OptConfig
from ..train.trainer import Trainer
from .mesh import make_mesh, make_production_mesh


def synthesize_corpus(ts: TokenStore, vocab: int, n_docs: int = 200,
                      seed: int = 0) -> int:
    rng = np.random.default_rng(seed)
    docs = [rng.integers(0, vocab, rng.integers(64, 2048))
            for _ in range(n_docs)]
    return ts.append_documents(docs, domain="synthetic")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-scale config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--mesh", default=None,
                    help="'production', 'multi-pod', or 'DxM' e.g. 2x4")
    ap.add_argument("--workdir", default="/tmp/repro_train")
    ap.add_argument("--data", default=None, help="TokenStore path")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = registry.get_reduced(args.arch) if args.reduced \
        else registry.get(args.arch)
    model = Model(cfg)
    if args.mesh in (None, "auto"):
        n = len(jax.devices())
        mesh = make_mesh((1, n) if n > 1 else (1, 1), ("data", "model"))
    elif args.mesh == "production":
        mesh = make_production_mesh()
    elif args.mesh == "multi-pod":
        mesh = make_production_mesh(multi_pod=True)
    else:
        d, m = (int(x) for x in args.mesh.split("x"))
        mesh = make_mesh((d, m), ("data", "model"))

    data_path = args.data or os.path.join(args.workdir, "tokens")
    ts = TokenStore(data_path, seq_len=args.seq, vocab=cfg.vocab)
    if ts.n_sequences < args.batch:
        n = synthesize_corpus(ts, cfg.vocab)
        print(f"synthesized {n} sequences into {data_path}")

    loader = ShardedLoader(ts.db, batch_size=args.batch)

    def batches():
        epoch = 0
        while True:
            got = False
            for b in loader.epoch(epoch):
                got = True
                batch = {"tokens": b}
                if cfg.frontend is not None or cfg.family == "encdec":
                    from ..models.frontends import synthetic_embeds
                    batch["embeds"] = synthetic_embeds(cfg, b.shape[0])
                yield batch
            epoch += 1
            if not got:
                raise RuntimeError("empty token store")

    trainer = Trainer(model, mesh,
                      OptConfig(lr=args.lr, warmup_steps=10,
                                total_steps=args.steps),
                      ckpt_dir=os.path.join(args.workdir, "ckpt"),
                      metrics_dir=os.path.join(args.workdir, "metrics"),
                      microbatches=args.microbatches)
    out = trainer.run(batches(), steps=args.steps)
    print(f"done: steps={out['steps']} final_loss={out['final_loss']:.4f} "
          f"(first={out['history'][0]:.4f})")
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())

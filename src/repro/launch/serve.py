"""Serving launcher: generation or retrieval-augmented serving.

  PYTHONPATH=src python -m repro.launch.serve --arch yi-6b --reduced \
      --mode generate --batch 4 --prompt-len 32 --max-new 16
  PYTHONPATH=src python -m repro.launch.serve --arch yi-6b --reduced \
      --mode retrieval --corpus 4096 --queries 64
"""
from __future__ import annotations

import argparse


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--mode", choices=("generate", "retrieval"),
                    default="generate")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--corpus", type=int, default=4096)
    ap.add_argument("--queries", type=int, default=64)
    ap.add_argument("--radius", type=float, default=0.3)
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config, reduced_config
    from repro.data import lm_batch
    from repro.models import init_params
    from repro.models.parallel import ParallelConfig
    from repro.serve import RetrievalConfig, RetrievalService, generate

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced_config(cfg)
    par = ParallelConfig(mesh=None, attn_chunk_q=64, attn_chunk_k=64,
                         logits_chunk=128)
    params = init_params(cfg, jax.random.PRNGKey(0))

    if args.mode == "generate":
        batch = lm_batch(0, 0, batch=args.batch, seq=args.prompt_len,
                         vocab=cfg.vocab, cfg=cfg)
        batch.pop("labels")
        toks = generate(params, batch, cfg, par,
                        cache_len=args.prompt_len + args.max_new,
                        max_new_tokens=args.max_new)
        print("generated:", toks.shape)
        print(toks[:2])
    else:
        svc = RetrievalService(cfg, par, params,
                               RetrievalConfig(radius=args.radius))
        corpus_batches = []
        bs = 64
        for i in range(args.corpus // bs):
            b = lm_batch(1, i, batch=bs, seq=args.prompt_len,
                         vocab=cfg.vocab, cfg=cfg)
            b.pop("labels")
            corpus_batches.append(b)
        n = svc.index_corpus(corpus_batches)
        qb = lm_batch(2, 0, batch=args.queries, seq=args.prompt_len,
                      vocab=cfg.vocab, cfg=cfg)
        qb.pop("labels")
        res, _ = svc.query(qb)
        sizes = [len(res.neighbors(i)) for i in range(res.n_queries)]
        print(f"indexed {n} docs; {args.queries} queries; "
              f"mean output size {sum(sizes)/len(sizes):.1f}; "
              f"frac linear {res.frac_linear:.2f}")
        print("service stats:", svc.stats)


if __name__ == "__main__":
    main()

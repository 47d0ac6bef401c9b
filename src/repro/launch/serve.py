"""Serving driver: batched request serving with continuous batching.

  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --reduced \
      --requests 16 --slots 4 --max-seq 128
"""
from __future__ import annotations

import argparse
import time
from typing import List

import numpy as np
import jax

from repro.configs import get_spec, reduced_model
from repro.models import model_zoo as zoo
from repro.models import params as params_lib
from repro.runtime.compile_cache import enable_compile_cache
from repro.serving.engine import Request, ServingEngine


def run(argv=None) -> List[Request]:
    """Serve a seeded random request stream; returns the finished
    requests (with their greedy ``out_tokens``)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    spec = get_spec(args.arch)
    cfg = reduced_model(spec.model) if args.reduced else spec.model
    params = params_lib.initialize(zoo.param_template(cfg),
                                   jax.random.PRNGKey(args.seed))
    engine = ServingEngine(cfg, params, slots=args.slots,
                           max_seq=args.max_seq)
    rng = np.random.default_rng(args.seed)
    t0 = time.monotonic()
    for i in range(args.requests):
        plen = int(rng.integers(4, args.max_seq // 4))
        engine.submit(Request(
            rid=i, prompt=rng.integers(1, cfg.vocab_size, plen).astype(np.int32),
            max_new_tokens=args.max_new))
    done = engine.run_until_drained()
    dt = time.monotonic() - t0
    total_new = sum(len(r.out_tokens) for r in done)
    ttfts = [r.first_token_at - r.submitted_at for r in done]
    print(f"served {len(done)} requests, {total_new} tokens in {dt:.2f}s "
          f"({total_new / dt:.1f} tok/s), "
          f"TTFT p50={np.percentile(ttfts, 50):.2f}s "
          f"p99={np.percentile(ttfts, 99):.2f}s")
    return done


def main(argv=None) -> int:
    run(argv)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

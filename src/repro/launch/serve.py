"""Serving launcher CLI: continuous-batching engine demo.

  PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m \
      --reduced --requests 8
"""
from __future__ import annotations

import argparse
import time

import numpy as np


def main() -> None:
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max_new_tokens", type=int, default=16)
    ap.add_argument("--max_len", type=int, default=256)
    args = ap.parse_args()

    from repro.configs import get_config
    from repro.models import build_model
    from repro.serve.engine import Request, ServeEngine

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if cfg.encoder_layers or cfg.num_vision_tokens:
        raise SystemExit("serve CLI demo supports decoder-only archs; "
                         "multimodal prefill needs frames/vision inputs")
    model = build_model(cfg)
    params = model.init(0)
    eng = ServeEngine(model, params, max_slots=args.slots,
                      max_len=args.max_len, eos_id=-1)
    rng = np.random.default_rng(0)
    for rid in range(args.requests):
        prompt = rng.integers(4, cfg.vocab_size,
                              size=int(rng.integers(8, 32))).astype(np.int32)
        eng.submit(Request(rid, prompt,
                           max_new_tokens=args.max_new_tokens))
    t0 = time.perf_counter()
    ticks = 0
    while (eng.queue or any(s is not None for s in eng.slots)) \
            and ticks <= 10_000:
        eng.step()
        ticks += 1
    dt = time.perf_counter() - t0
    if len(eng.finished) < args.requests:
        raise SystemExit(f"only {len(eng.finished)} of {args.requests} "
                         f"requests served after {ticks} ticks")
    total = args.requests * args.max_new_tokens
    print(f"served {args.requests} requests in {ticks} ticks "
          f"({eng.steps} batched decode steps, {total/dt:.1f} tok/s)")


if __name__ == "__main__":
    main()

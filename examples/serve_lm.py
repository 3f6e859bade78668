"""End-to-end serving driver: batched requests through prefill + decode
with continuous slot batching (reduced gemma3 config exercises the
local:global ring-buffer cache path).

Run:  PYTHONPATH=src python examples/serve_lm.py
"""

import argparse
import time

import jax
import numpy as np

from repro.configs import registry
from repro.models import api
from repro.serve.engine import Request, ServeEngine
from repro.launch.cache import use_compile_cache


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-27b",
                    choices=registry.ARCH_IDS)
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--batch", type=int, default=3)
    ap.add_argument("--max-new", type=int, default=12)
    args = ap.parse_args()

    cfg = registry.reduced_config(registry.get_config(args.arch))
    model = api.build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    print(f"serving reduced {args.arch}: "
          f"{cfg.param_count() / 1e6:.1f}M params (smoke scale)")

    rng = np.random.default_rng(0)
    reqs = [Request(rid=i,
                    prompt=rng.integers(
                        0, cfg.vocab_size,
                        size=int(rng.integers(4, 12))).astype(np.int32),
                    max_new_tokens=args.max_new)
            for i in range(args.requests)]
    engine = ServeEngine(model, params, batch=args.batch, max_len=64)
    t0 = time.perf_counter()
    results = engine.run(reqs)
    dt = time.perf_counter() - t0
    total_new = sum(len(v) for v in results.values())
    for rid in sorted(results):
        print(f"req {rid}: {results[rid][:8]}...")
    print(f"{len(results)} requests, {total_new} tokens "
          f"in {dt:.2f}s ({total_new / dt:.1f} tok/s)")
    assert len(results) == args.requests
    print("serve_lm OK")


if __name__ == "__main__":
    main()

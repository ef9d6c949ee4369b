"""Serving launcher: batched greedy decoding over a prompt file or synthetic
requests.

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m \
        --requests 8 --max-new 16
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.serving.engine import Request, ServeEngine
from repro.sharding.specs import Topology


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--batch-size", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--full", dest="reduced", action="store_false",
                    help="published widths (default: the reduced config)")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    api = build_model(cfg)
    params = api.init(jax.random.key(0))
    eng = ServeEngine(
        api, params, Topology(mesh=None),
        batch_size=args.batch_size, max_len=args.max_len,
    )
    rng = np.random.default_rng(0)
    reqs = []
    for rid in range(args.requests):
        plen = int(rng.integers(4, 24))
        r = Request(
            rid=rid,
            prompt=rng.integers(2, cfg.vocab_size, size=plen).astype(np.int32),
            max_new_tokens=args.max_new,
        )
        reqs.append(r)
        eng.submit(r)
    t0 = time.perf_counter()
    eng.run_until_drained()
    dt = time.perf_counter() - t0
    total = sum(len(r.generated) for r in reqs)
    print(f"served {len(reqs)} requests / {total} tokens in {dt:.2f}s "
          f"({total/dt:.1f} tok/s batched greedy)")
    for r in reqs[:4]:
        print(f"  req {r.rid}: {len(r.generated)} tokens {r.generated[:8]}...")


if __name__ == "__main__":
    main()

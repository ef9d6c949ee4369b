"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch mamba2-130m \
        --steps 100 --batch 8 --seq 128 [--full] [--mesh local] \
        [--ckpt-dir DIR]

On a real pod this runs under one process per host with jax.distributed
initialized by the cluster runtime; the mesh/topology code is identical.
By default it runs the reduced config; ``--full`` asks for the published
widths, and ``--mesh local`` puts every local device on the data axis.
"""

from __future__ import annotations

import argparse
import os
import tempfile

import numpy as np

from repro import perf_flags
from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.data.pipeline import DataConfig, batches
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh, make_production_mesh
from repro.models import build_model
from repro.optim.adamw import AdamWConfig
from repro.runtime.fault import FailureInjector
from repro.runtime.train_loop import Trainer, TrainerConfig
from repro.sharding.specs import Topology, make_topology


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--full", dest="reduced", action="store_false",
                    help="published widths (default: the reduced config)")
    ap.add_argument("--ckpt-dir",
                    default=os.path.join(tempfile.gettempdir(), "repro_ckpt"))
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--mesh", choices=["none", "local", "production"],
                    default="none")
    ap.add_argument(
        "--offload-engine", action="store_true",
        help="dispatch the step's gradient/metric collectives through the "
             "offload engine as planned descriptors (pure-DP meshes)",
    )
    ap.add_argument("--fail-at", type=int, nargs="*", default=[],
                    help="inject simulated failures at these steps")
    ap.add_argument("--opt", default="", help="perf flags k=v,...")
    return ap.parse_args(argv)


def build_trainer(args: argparse.Namespace) -> Trainer:
    """The Trainer the command line describes (compile cache on)."""
    enable_compile_cache()
    perf_flags.parse_opt_string(args.opt)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    api = build_model(cfg)
    if args.mesh == "production":
        topo = make_topology(make_production_mesh())
    elif args.mesh == "local":
        topo = make_topology(make_local_mesh())
    else:
        topo = Topology(mesh=None)

    shape = ShapeConfig("cli", args.seq, args.batch, "train")
    data = batches(DataConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq,
        global_batch=args.batch,
    ))
    return Trainer(
        api, topo, shape, data,
        TrainerConfig(
            ckpt_dir=args.ckpt_dir, ckpt_every=25,
            use_offload_engine=args.offload_engine,
        ),
        AdamWConfig(lr=args.lr, warmup_steps=10, total_steps=args.steps),
        injector=FailureInjector(fail_at=tuple(args.fail_at)),
    )


def main() -> None:
    args = parse_args()
    tr = build_trainer(args)
    params, opt = tr.init_state()
    start, params, opt = tr.maybe_restore(params, opt)
    if start:
        print(f"resumed from checkpoint at step {start}")
    params, opt, hist = tr.run(params, opt, args.steps, start_step=start)
    for h in hist[:: max(1, len(hist) // 12)]:
        print(f"step {h['step']:5d} loss {h['loss']:.4f} "
              f"gnorm {h['grad_norm']:.2f} {h['step_time_s']*1e3:.0f}ms")
    print(f"final loss: {hist[-1]['loss']:.4f}; "
          f"remesh events: {len(tr.remesh_events)}; "
          f"straggler flags: {len(tr.straggler.events)}")


if __name__ == "__main__":
    main()

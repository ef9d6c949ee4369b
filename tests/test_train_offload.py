"""Offloaded training path: engine-dispatched DP step vs raw shard_map.

The heavy end-to-end scenarios (bitwise step equivalence on a 2x2 mesh,
planner-first remesh adoption, plan-vs-halving) run in a subprocess via
``repro.testing.train_offload_check`` (the multi-device CPU mesh must exist
before jax import). The in-process tests cover the build-time contracts.
"""

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.launch.steps import build_dp_train_step, build_train_step
from repro.models import build_model
from repro.sharding.specs import Topology, make_topology


def test_trainer_offload_end_to_end(subprocess_runner):
    """2-step DP trainer on a 2x2 (pod, data) mesh: gradient allreduce /
    metric means / example EXSCAN through OffloadEngine planned descriptors,
    bitwise-equal to the raw-lax shard_map baseline; step-2 dispatches hit
    the plan cache; an injected failure adopts plan_remesh's topology and
    repopulates the engine cache on the surviving mesh."""
    subprocess_runner("repro.testing.train_offload_check", "2", "2")


def test_build_train_step_flag_requires_engine():
    cfg = get_config("smollm_360m").reduced()
    api = build_model(cfg)
    shape = ShapeConfig("tiny", 16, 4, "train")
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                             ("pod", "data"))
    topo = make_topology(mesh)
    with pytest.raises(ValueError, match="OffloadEngine"):
        build_train_step(api, topo, shape, use_offload_engine=True)


def test_build_train_step_flag_noop_without_mesh():
    cfg = get_config("smollm_360m").reduced()
    api = build_model(cfg)
    shape = ShapeConfig("tiny", 16, 4, "train")
    step, shapes, specs = build_train_step(
        api, Topology(mesh=None), shape, use_offload_engine=True
    )
    assert step is not None  # fell back to the jitted GSPMD path


def test_trainer_engine_without_mesh_raises(tmp_path):
    """The Trainer refuses the engine without a mesh instead of dropping it."""
    from repro.runtime.train_loop import Trainer, TrainerConfig

    api = build_model(get_config("smollm_360m").reduced())
    shape = ShapeConfig("tiny", 16, 4, "train")
    with pytest.raises(ValueError, match="needs a mesh"):
        Trainer(
            api, Topology(mesh=None), shape, iter(()),
            TrainerConfig(ckpt_dir=str(tmp_path), use_offload_engine=True),
        )


def test_train_cli_mesh_and_width_flags():
    """--full is the only way to published widths; --mesh local exists."""
    from repro.launch.train import parse_args

    args = parse_args(["--arch", "mamba2-130m"])
    assert args.reduced and args.mesh == "none"
    args = parse_args(["--arch", "mamba2-130m", "--full", "--mesh", "local"])
    assert not args.reduced and args.mesh == "local"


def test_dp_step_rejects_tensor_parallel_mesh():
    cfg = get_config("smollm_360m").reduced()
    api = build_model(cfg)
    shape = ShapeConfig("tiny", 16, 4, "train")

    class _FakeTopo:
        mesh = object()
        model_size = 2

    with pytest.raises(ValueError, match="data-parallel only"):
        build_dp_train_step(api, _FakeTopo(), shape)


def test_make_topology_pure_dp_pod_mesh():
    mesh = jax.sharding.Mesh(
        np.array(jax.devices()[:1]).reshape(1, 1), ("pod", "data")
    )
    topo = make_topology(mesh)
    assert topo.batch_axes == ("pod", "data")
    assert topo.model_axis is None
    assert topo.model_size == 1
    assert topo.dp_size == 1


def test_trainer_step_compiles_once(tmp_path):
    """The state init_state hands out carries the shardings the step
    returns it with, so the second step reuses the first step's program."""
    from repro.launch import train

    args = train.parse_args([
        "--arch", "mamba2-130m", "--mesh", "local", "--steps", "2",
        "--batch", "2", "--seq", "32", "--ckpt-dir", str(tmp_path),
    ])
    tr = train.build_trainer(args)
    params, opt = tr.init_state()
    tr.run(params, opt, 2)
    assert tr.step_fn._cache_size() == 1

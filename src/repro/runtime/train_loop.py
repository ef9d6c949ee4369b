"""Fault-tolerant training driver.

Checkpoint/restart + failure handling + elastic re-mesh + straggler watch,
composed over the pure step builders in launch/steps.py. The loop's contract:

  1. every ``ckpt_every`` steps: atomic async checkpoint (params+opt+step);
  2. a step raising SimulatedFailure — or any error in the jax collective
     runtime-error family (``fault.RECOVERABLE_ERRORS``) — triggers the
     planner-first recovery sequence:
     fail -> ``plan_remesh`` (lost_hosts derived from the failure) ->
     **adopt the planned sizes** -> rebuild the step on the surviving
     topology -> ``notify_remesh`` (offload listeners clear plan caches and
     re-tune against the adopted mesh) -> restore the latest checkpoint with
     NEW shardings -> continue (bounded retries). The offload engine's
     cleared cache then repopulates from the trainer's own descriptors on
     the next step.
  3. StragglerDetector watches step wall-times; eviction recommendations
     feed the same re-mesh path.

With ``TrainerConfig.use_offload_engine`` the step's gradient/metric
collectives dispatch through an :class:`~repro.offload.OffloadEngine`
(see ``launch.steps.build_dp_train_step``); otherwise GSPMD derives them.

Works identically on the 1-device CPU smoke mesh and on a real pod — the
fault-injection integration test (tests/test_fault_tolerance.py) runs the
whole recovery path on CPU.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Iterator, Optional

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.checkpoint.manager import CheckpointManager
from repro.configs.base import ModelConfig, ShapeConfig
from repro.launch.steps import build_train_step
from repro.models import ModelApi, build_model
from repro.optim.adamw import AdamWConfig, init_opt_state
from repro.runtime import fault as fault_mod
from repro.runtime.fault import (
    RECOVERABLE_ERRORS,
    FailureInjector,
    SimulatedFailure,
    is_recoverable,
    plan_remesh,
)
from repro.runtime.straggler import StragglerDetector
from repro.sharding.specs import Topology, make_topology, use_topology


@dataclasses.dataclass
class TrainerConfig:
    ckpt_dir: str
    ckpt_every: int = 50
    keep_ckpts: int = 3
    max_retries: int = 3
    log_every: int = 10
    async_ckpt: bool = True
    #: route the step's gradient/metric collectives through the offload
    #: engine as planned descriptors (requires a pure-DP mesh)
    use_offload_engine: bool = False


class Trainer:
    def __init__(
        self,
        api: ModelApi,
        topo: Topology,
        shape: ShapeConfig,
        data_iter: Iterator[Dict[str, np.ndarray]],
        tcfg: TrainerConfig,
        opt_cfg: Optional[AdamWConfig] = None,
        injector: Optional[FailureInjector] = None,
        engine: Any = None,
    ):
        self.api = api
        self.topo = topo
        self.shape = shape
        self.data_iter = data_iter
        self.tcfg = tcfg
        self.opt_cfg = opt_cfg or AdamWConfig()
        self.injector = injector
        self.engine = engine
        self.ckpt = CheckpointManager(
            tcfg.ckpt_dir, keep=tcfg.keep_ckpts, async_write=tcfg.async_ckpt
        )
        self.straggler = StragglerDetector()
        self.remesh_events: list = []
        self._build()

    def _build(self):
        use_engine = self.tcfg.use_offload_engine
        if use_engine and self.topo.mesh is None:
            raise ValueError(
                "use_offload_engine needs a mesh to dispatch over; "
                "build the Trainer with a meshed topology"
            )
        if use_engine and self.engine is None:
            from repro.launch.offload_runtime import build_offload_engine

            self.engine = build_offload_engine()
        self.step_fn, _, self.specs = build_train_step(
            self.api, self.topo, self.shape, self.opt_cfg,
            use_offload_engine=use_engine,
            engine=self.engine if use_engine else None,
        )

    def init_state(self, seed: int = 0):
        with use_topology(self.topo):
            params = self.api.init(jax.random.key(seed))
            opt_state = init_opt_state(params)
        if self.topo.mesh is not None:
            # commit the state to the shardings the step hands it back
            # with: uncommitted state compiles the step again on call two
            shardings = jax.tree.map(
                lambda s: NamedSharding(self.topo.mesh, s), self.specs[:2],
                is_leaf=lambda x: isinstance(x, PartitionSpec),
            )
            params, opt_state = jax.device_put((params, opt_state), shardings)
        return params, opt_state

    def maybe_restore(self, params, opt_state):
        latest = self.ckpt.latest_step()
        if latest is None:
            return 0, params, opt_state
        _, blob = self.ckpt.restore(
            {"params": params, "opt": opt_state}, step=latest
        )
        return latest, blob["params"], blob["opt"]

    # ------------------------------------------------------------------ run
    def run(self, params, opt_state, num_steps: int, start_step: int = 0):
        """Returns (final_params, final_opt, history). Fault-tolerant."""
        history = []
        step = start_step
        retries = 0
        while step < num_steps:
            batch = next(self.data_iter)
            t0 = time.perf_counter()
            try:
                if self.injector is not None:
                    self.injector.check(step)
                with use_topology(self.topo):
                    params, opt_state, metrics = self.step_fn(
                        params, opt_state, batch
                    )
                    metrics = jax.tree.map(float, metrics)
            except RECOVERABLE_ERRORS as e:
                if not is_recoverable(e):
                    raise  # OOM / shape bugs: remeshing would mask them
                retries += 1
                if retries > self.tcfg.max_retries:
                    raise
                self._recover(e)
                step, params, opt_state = self._restore_after_failure(
                    params, opt_state
                )
                continue
            dt = time.perf_counter() - t0
            verdict = self.straggler.observe(step, dt)
            metrics["step_time_s"] = dt
            metrics["straggler_flagged"] = verdict["flagged"]
            history.append({"step": step, **metrics})
            step += 1
            if step % self.tcfg.ckpt_every == 0 or step == num_steps:
                self.ckpt.save(
                    step, {"params": params, "opt": opt_state},
                    block=(step == num_steps),
                )
        self.ckpt.wait()
        return params, opt_state, history

    # ------------------------------------------------------------- recovery
    def _recover(self, err: Exception) -> None:
        """Planner-first elastic re-mesh: adopt what ``plan_remesh`` returns.

        Sequence: derive ``lost_hosts`` from the failure -> ``plan_remesh``
        -> adopt the planned data-axis size (every other axis is
        load-bearing and kept) -> rebuild the step on the adopted topology
        -> ``notify_remesh`` so offload listeners invalidate plan caches and
        re-tune against the mesh that was *actually* adopted. Notify fires
        only after adopt+rebuild: listeners re-tune on the new topology, and
        the engine's cleared cache repopulates from the rebuilt step's own
        descriptors on the next step.
        """
        from repro.obs import events as obs_events

        obs_events.record("recovery", error=str(err)[:200])
        obs_events.auto_dump("recovery")
        mesh = self.topo.mesh
        if mesh is None:
            self.remesh_events.append({"err": str(err), "action": "none"})
            return
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        old_data = int(sizes.get("data", 1))
        rest = int(np.prod([s for a, s in sizes.items() if a != "data"]))
        lost = max(1, int(getattr(err, "lost_hosts", 1)))
        plan = plan_remesh(old_data, rest, lost_hosts=lost)
        if plan is None:
            # the data axis cannot absorb the loss (e.g. TP-only mesh, or
            # lost_hosts was a pessimistic estimate): keep the topology and
            # retry from the checkpoint — run()'s max_retries bounds this
            self.remesh_events.append(
                {"err": str(err), "action": "infeasible", "lost_hosts": lost}
            )
            return
        new_data = int(plan[0])
        old_axes = tuple(int(s) for s in mesh.devices.shape)
        new_sizes = {**sizes, "data": new_data}
        new_shape = tuple(int(new_sizes[a]) for a in mesh.axis_names)
        n_needed = int(np.prod(new_shape))
        devices = np.asarray(mesh.devices).reshape(-1)[:n_needed]
        new_mesh = jax.sharding.Mesh(
            devices.reshape(new_shape), mesh.axis_names
        )
        self.topo = make_topology(new_mesh)
        self._build()
        # adopt + rebuild first, *then* tell the offload layer: plan caches
        # and the tuning grid are invalidated against the adopted topology
        fault_mod.notify_remesh(old_axes, new_shape)
        self.remesh_events.append(
            {"err": str(err), "old_data": old_data, "new_data": new_data,
             "plan": plan, "adopted": new_shape, "lost_hosts": lost}
        )

    def _restore_after_failure(self, params, opt_state):
        self.ckpt.wait()
        latest = self.ckpt.latest_step()
        if latest is None:
            with use_topology(self.topo):
                params = self.api.init(jax.random.key(0))
                opt_state = init_opt_state(params)
            return 0, params, opt_state
        host_params = jax.tree.map(np.asarray, params)
        host_opt = jax.tree.map(np.asarray, opt_state)
        _, blob = self.ckpt.restore(
            {"params": host_params, "opt": host_opt}, step=latest
        )
        return latest, blob["params"], blob["opt"]

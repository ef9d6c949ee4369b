"""The training step, driven as a training job drives it.

Set-up builds the program's ``Trainer`` (``repro.launch.train
.build_trainer``), gives it weights made on the device from the seed by the
yardstick's ``init_params`` and the packed-document feed, and drives its
step function through the first steps: those compile, and their losses,
step 1's gradient (from the optimizer's first moment) and the weights'
change over them are what the check compares with the plain reference. The
window then runs the same step function on the same state, one step in
flight, and counts the tokens of every step it completed.

Everything model-specific comes from the yardstick the configuration names
(``"reference"``: ``chipbench/<reference>.py``, see ``spec``): which fields
of the program's config must match, the weights' layout and draw, the plain
reference, the gaps read, the control and the limits.
"""

from __future__ import annotations

import time
from pathlib import Path
from typing import Any, Dict, List, Optional

import numpy as np

from chipbench import spec, traffic
from chipbench.systems import Check, Window, jax_key, window_spans


class TrainerCell:
    def __init__(
        self, config: Dict[str, Any], workload: Dict[str, Any], *, seed: int,
        seconds: float, devices, scratch: Path, tracing: bool = False,
    ):
        self.config, self.workload = config, workload
        self.seed, self.seconds = int(seed), float(seconds)
        self.devices = list(devices)
        self.scratch = Path(scratch)
        self.tracing = bool(tracing)
        self.ref = spec.load_yardstick(config["reference"])
        self.model, self.opt_cfg = config["model"], config["optimizer"]
        self.batch, self.seq = int(workload["batch"]), int(workload["seq_len"])
        self.limits = dict(self.ref.LIMITS, **config.get("limits", {}))

    def _feed(self):
        m = self.model
        return traffic.packed_batches(
            m["vocab_size"], self.seq, self.batch, self.seed,
            mean_doc_len=int(self.workload["mean_doc_len"]),
        )

    # ------------------------------------------------------------- set-up

    def setup(self) -> None:
        import jax
        from jax.sharding import NamedSharding, PartitionSpec

        from repro.launch import train
        from repro.obs import tracing
        from repro.optim.adamw import init_opt_state
        from repro.sharding.specs import use_topology

        if self.tracing and not tracing.get_tracer().enabled:
            tracing.install_tracer()
        o = self.opt_cfg
        argv = [
            "--arch", self.config["arch"], "--mesh", "local",
            "--steps", str(o["total_steps"]), "--batch", str(self.batch),
            "--seq", str(self.seq), "--lr", str(o["lr"]),
            "--ckpt-dir", str(self.scratch / "ckpt"), "--full",
        ]
        tr = train.build_trainer(train.parse_args(argv))
        self._check_program(tr)
        self.tr = tr
        tr.data_iter = self._feed()
        self._use_topology = use_topology

        mesh = tr.topo.mesh
        shard = lambda specs: jax.tree.map(  # noqa: E731
            lambda s: NamedSharding(mesh, s), specs,
            is_leaf=lambda x: isinstance(x, PartitionSpec))
        pshard, oshard = shard(tr.specs[0]), shard(tr.specs[1])
        key = jax_key(self.seed)
        init = jax.jit(lambda k: self.ref.init_params(k, self.model), out_shardings=pshard)
        params = init(key)
        opt = jax.jit(init_opt_state, out_shardings=oshard)(params)

        b1 = float(o["b1"])
        norms = jax.jit(lambda t: jax.tree.map(
            lambda a: jax.numpy.linalg.norm(a.ravel()) / (1 - b1), t))
        change = jax.jit(lambda master, k: jax.tree.map(
            lambda a, b: jax.numpy.linalg.norm((a - b.astype(a.dtype)).ravel()),
            master, self.ref.init_params(k, self.model)))
        losses, gnorms = [], []
        for step in range(int(self.workload["setup_steps"])):
            params, opt, metrics = self._step(params, opt)
            losses.append(metrics["loss"])
            gnorms.append(metrics.get("grad_norm", float("nan")))
            if step == 0:
                grad_norms = norms(opt["m"])
        change_norms = change(opt["master"], key)
        self.setup_readings = (
            [float(v) for v in losses],
            _by_path(jax.device_get(grad_norms)),
            _by_path(jax.device_get(change_norms)),
        )
        self.global_grad_norms = [float(v) for v in gnorms]
        self.params, self.opt = params, opt
        jax.block_until_ready((params, opt))

    def _check_program(self, tr) -> None:
        cfg = tr.api.cfg
        fields = self.ref.PROGRAM_FIELDS
        got = {k: getattr(cfg, k) for k in fields}
        want = {k: self.model[v] for k, v in fields.items()}
        if got != want:
            raise ValueError(f"the program's model config {got} is not the "
                             f"configuration's {want}")
        oc = tr.opt_cfg
        for k in ("lr", "b1", "b2", "eps", "weight_decay", "clip_norm",
                  "warmup_steps", "total_steps", "min_lr_ratio"):
            if float(getattr(oc, k)) != float(self.opt_cfg[k]):
                raise ValueError(f"the program's optimizer {k}={getattr(oc, k)} "
                                 f"is not the configuration's {self.opt_cfg[k]}")
        import jax

        want_shapes = self.ref.param_shapes(self.model)
        got_shapes = tr.api.param_shapes()
        if jax.tree.structure(got_shapes) != jax.tree.structure(want_shapes) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(got_shapes), jax.tree.leaves(want_shapes))
        ):
            raise ValueError("the program's weights do not have the reference's layout")

    def _step(self, params, opt):
        batch = next(self.tr.data_iter)
        with self._use_topology(self.tr.topo):
            return self.tr.step_fn(params, opt, batch)

    # ------------------------------------------------------------- window

    def counters(self) -> Dict[str, Any]:
        """The trainer keeps no counters of its own."""
        return {}

    def spans(self) -> Optional[list]:
        return window_spans(self.window_)

    def window(self) -> Window:
        import jax

        params, opt = self.params, self.opt
        self.params = self.opt = None
        losses: List[Any] = []
        t0 = time.perf_counter()
        t_end = t0 + self.seconds
        pending = None
        now = t0
        steps = 0
        while now < t_end:
            params, opt, metrics = self._step(params, opt)
            if pending is not None:
                jax.block_until_ready(pending)
                steps += 1
            pending = metrics["loss"]
            losses.append(pending)
            now = time.perf_counter()
        jax.block_until_ready(pending)
        steps += 1
        te = time.perf_counter()
        self.params, self.opt = params, opt
        finite = np.isfinite(np.asarray(jax.device_get(losses)))
        win = Window(start=t0, end=te, attempted=steps, failed=int((~finite).sum()))
        win.tokens = steps * self.batch * self.seq
        win.correct_in_window = int(finite.sum())
        win.info = {"step_s": (te - t0) / steps, "window_losses_first_last":
                    [float(losses[0]), float(losses[-1])]}
        self.window_ = win
        return win

    # ------------------------------------------------------------- check

    def release(self) -> None:
        self.params = self.opt = self.tr = None

    def _reference(self, q=None, rows: Optional[int] = None):
        """(losses, step 1's gradient norms, change norms) of the plain
        reference from the same weights and batches, and the reference."""
        import jax

        ref = self.ref.Reference(self.model, self.opt_cfg,
                                 int(self.workload["reference_rows_per_block"]), q)
        params = jax.jit(lambda k: self.ref.init_params(k, self.model))(jax_key(self.seed))
        return ref.run(params, self._reference_batches(), rows), ref

    def _reference_batches(self):
        return traffic.take(self._feed(), int(self.workload["setup_steps"]))

    def check(self, control: bool = False) -> List[Check]:
        """The program's first steps against the float32 reference. With
        ``control`` the reference with the yardstick's ``control`` rounding
        stands in the program's place, and the readings of two faults
        planted in the reference are kept beside it: half of the batch left
        out, and the state left unchanged."""
        want, ref = self._reference()
        if control:
            got, low = self._reference(q=self.ref.control)
            got_gnorms = low.global_grad_norms
        else:
            got, got_gnorms = self.setup_readings, self.global_grad_norms
        readings = self.ref.gaps(got, want)
        info = self.window_.info
        info.update(
            readings=readings, reference_losses=want[0], program_losses=got[0],
            global_grad_norms={"program": got_gnorms, "reference": ref.global_grad_norms},
            leaf_norms={k: {"grad": [got[1][k], want[1][k]],
                            "change": [got[2][k], want[2][k]]} for k in want[1]},
        )
        if control:
            info["control"] = (f"the reference with {self.ref.control.__name__} "
                               "contractions in the program's place")
            info["fault_half_batch"] = self._fault_readings(
                self._reference(rows=self.batch // 2)[0], want)
            info["fault_state_unchanged"] = self._fault_readings(
                self._unchanged(ref, want), want)
        return [Check(k, readings[k], limit) for k, limit in self.limits.items()]

    def _unchanged(self, ref, want):
        """What a step that returns its state unchanged reads: every loss
        taken at the first weights, no first moment, no change."""
        import jax

        params = jax.jit(lambda k: self.ref.init_params(k, self.model))(jax_key(self.seed))
        params = jax.tree.map(lambda a: a.astype(jax.numpy.float32), params)
        losses = [float(ref.loss_and_grads(params, b)[0]) for b in self._reference_batches()]
        zeros = {k: 0.0 for k in want[1]}
        return losses, zeros, dict(zeros)

    def _fault_readings(self, got, want) -> Dict[str, Any]:
        out = dict(self.ref.gaps(got, want), losses=got[0])
        out["leaf_grad_norms"] = got[1]
        return out


def _by_path(tree) -> Dict[str, float]:
    import jax

    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(p): float(v) for p, v in flat}


Cell = TrainerCell

"""The offload engine and its broker, driven as their users drive them.

``entry: "service"`` (open loop): tenants submit wire-encoded descriptors
through ``ServiceClient.submit`` of ``build_offload_service()``; each
request is timed from its due time to its result on the tenant's side.

``entry: "engine"`` (closed loop): one caller issues
``OffloadEngine.offload`` back to back on inputs already on the device,
each timed from the call to ``block_until_ready`` on its output.

The configuration's ``mode`` says where the ranks are. ``"sim"``: stacked
on one chip. ``"driver"`` (engine entry only): one rank on each chip of a
flat mesh over the cell's chips (``launch.mesh.auto_mesh``); the engine
runs its own ``jit(shard_map(...))`` (``offload(..., axis_name, mesh)``),
and each input is placed before the window with its rank's row on that
rank's chip.

Every answer of the open loop, and a reservoir sample drawn from the seed
of the closed loop's, is compared bit for bit with ``reference.collective``
once the window has closed.
"""

from __future__ import annotations

import collections
import threading
import time
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from chipbench import reference, traffic
from chipbench.systems import Check, Window, jax_key, window_spans

#: each request's result is awaited this long past the window's close
LATE_S = 60.0
#: how often the open loop's collector looks for answers
POLL_S = 0.0002


def _wire_dtype(name: str):
    from repro.core.packet import WireDType

    return WireDType[name.upper()]


class OffloadCell:
    def __init__(
        self, config: Dict[str, Any], workload: Dict[str, Any], *, seed: int,
        seconds: float, devices, scratch: Path, tracing: bool = False,
    ):
        self.config, self.workload = config, workload
        self.seed, self.seconds = int(seed), float(seconds)
        self.devices = list(devices)
        self.scratch = Path(scratch)
        self.tracing = bool(tracing)
        self.rate = float(workload.get("rate_per_s", 0))
        self.p = int(config["ranks"])
        self.entry = workload["entry"]
        self.svc = None
        self.engine = None
        self.setup_phases: Dict[str, float] = {}
        self._t = time.perf_counter()

    def _mark(self, phase: str) -> None:
        now = time.perf_counter()
        self.setup_phases[phase] = now - self._t
        self._t = now

    # ------------------------------------------------------------- set-up

    def setup(self) -> None:
        import jax
        from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

        mode = self.config["mode"]
        if mode == "sim":
            self.place = SingleDeviceSharding(self.devices[0])
            self.offload_kw: Dict[str, Any] = {}
        elif mode == "driver":
            from repro.launch.mesh import auto_mesh

            if self.entry != "engine" or len(self.devices) != self.p:
                raise ValueError("driver mode runs the engine entry with one "
                                 f"rank on each chip: {self.p} ranks, "
                                 f"{len(self.devices)} chips, entry {self.entry!r}")
            mesh = auto_mesh((self.p,), ("ranks",), devices=self.devices)
            self.place = NamedSharding(mesh, PartitionSpec("ranks"))
            self.offload_kw = {"axis_name": "ranks", "mesh": mesh}
        else:
            raise ValueError(f"unknown engine mode {mode!r}")
        self._jax = jax
        if self.entry == "service":
            self._setup_service()
        elif self.entry == "engine":
            self._setup_engine()
        else:
            raise ValueError(f"unknown entry {self.entry!r}")

    def _engine_kw(self) -> Dict[str, Any]:
        return dict(
            retune_on_remesh=False, autotune_if_missing=False,
            tracing=self.tracing,
        )

    def _setup_service(self) -> None:
        from repro.launch.offload_runtime import build_offload_service

        cfg, wl = self.config, self.workload
        svc = build_offload_service(
            registry=self.scratch / "registry",
            flush_interval_s=cfg["flush_interval_s"],
            max_coalesce=cfg["max_coalesce"], max_pending=cfg["max_pending"],
            max_tenants=cfg["max_tenants"], start=False, **self._engine_kw(),
        )
        self.svc, self.engine = svc, svc.engine
        self._mark("build")
        self.shapes = traffic.tenant_shapes(wl)
        self.due, self.tenant = traffic.open_loop_schedule(
            wl, self.seed, self.seconds, self.rate
        )
        distinct = sorted(set(self.shapes), key=lambda s: (s.coll, s.op, s.bytes_per_rank))
        self.shape_id = {s: i for i, s in enumerate(distinct)}
        self.descs = [
            svc.make_descriptor(
                s.coll, p=self.p, payload_bytes=s.count * 4, op=s.op,
                data_type=_wire_dtype(s.dtype),
            )
            for s in distinct
        ]
        words = [d.encode() for d in self.descs]
        # one payload per arrival, made in bulk per shape, placed up front
        n = len(self.due)
        req_shape = np.array([self.shape_id[self.shapes[t]] for t in self.tenant], dtype=np.int64)
        self.req_shape = req_shape
        self.host_x: List[Optional[np.ndarray]] = [None] * n
        rng = traffic.rng_for(self.seed, 5)
        for sid, s in enumerate(distinct):
            idx = np.flatnonzero(req_shape == sid)
            block = traffic.host_payloads(rng, max(1, len(idx)), self.p, s)
            for j, k in enumerate(idx):
                self.host_x[k] = block[j]
        self._mark("payloads")
        self.dev_x = self._jax.device_put(self.host_x, self.place) if n else []
        self._mark("placement")
        self.words = [words[sid] for sid in req_shape]
        # warm every fused width each shape can be dispatched at
        warm_x = [
            self._jax.device_put(traffic.host_payloads(rng, 1, self.p, s)[0], self.place)
            for s in distinct
        ]
        widths = []
        w = 1
        while w <= cfg["max_coalesce"]:
            widths.append(w)
            w *= 2
        warm = svc.client("warmup", max_queue_depth=2 * cfg["max_coalesce"])
        for sid in range(len(distinct)):
            for w in widths:
                tickets = [warm.submit(words[sid], warm_x[sid]) for _ in range(w)]
                svc.drain()
                for t in tickets:
                    self._jax.block_until_ready(t.result(LATE_S))
        warm.close()
        self._mark("warm_up")
        self.clients = [
            svc.client(f"tenant{i}", max_queue_depth=wl["max_queue_depth"])
            for i in range(int(wl["tenants"]))
        ]
        self.results: List[Any] = [None] * n
        self.done_t = np.full(n, np.nan)
        self.errors: Dict[int, str] = {}
        self.submitted: "collections.deque" = collections.deque()
        svc.start()

    def _collect(self, hard_deadline: float, stop: threading.Event) -> None:
        """One thread stamps every answer as it lands, polling the tickets
        submitted so far every ``POLL_S``: an answer's time is late by at
        most one poll."""
        pending: List[Tuple[int, Any]] = []
        while True:
            while self.submitted:
                pending.append(self.submitted.popleft())
            now = time.perf_counter()
            still = []
            for k, ticket in pending:
                if ticket.done():
                    self.done_t[k] = now
                    try:
                        self.results[k] = ticket.result(0)
                    except Exception as e:  # noqa: BLE001 - failed request
                        self.errors[k] = repr(e)
                else:
                    still.append((k, ticket))
            pending = still
            if stop.is_set() and not pending and not self.submitted:
                return
            if now > hard_deadline:
                for k, _ in pending:
                    self.errors[k] = "no answer"
                return
            time.sleep(POLL_S)

    def _setup_engine(self) -> None:
        import jax

        from repro.launch.offload_runtime import build_offload_engine

        wl = self.workload
        self.engine = eng = build_offload_engine(**self._engine_kw())
        self._mark("build")
        self.variants = traffic.closed_loop_variants(wl)
        self.descs = [
            eng.make_descriptor(
                v.coll, p=self.p, payload_bytes=v.count * 4, op=v.op,
                data_type=_wire_dtype(v.dtype),
            )
            for v in self.variants
        ]
        k_in = int(wl["inputs_per_variant"])
        self.inputs: List[List[Any]] = []
        self.host_inputs: Dict[Tuple[int, int], np.ndarray] = {}
        rng = traffic.rng_for(self.seed, 5)
        for vi, v in enumerate(self.variants):
            if self.p * v.count * 4 > (1 << 24):
                self.inputs.append(self._device_inputs(vi, v, k_in))
            else:
                block = traffic.host_payloads(rng, k_in, self.p, v)
                for k in range(k_in):
                    self.host_inputs[(vi, k)] = block[k]
                self.inputs.append(jax.device_put(list(block), self.place))
        jax.block_until_ready(self.inputs)
        self._mark("payloads")
        for _ in range(2):
            for vi, d in enumerate(self.descs):
                jax.block_until_ready(eng.offload(d, self.inputs[vi][0], **self.offload_kw))
        self._mark("warm_up")

    def _device_inputs(self, vi: int, v: traffic.Shape, k_in: int) -> List[Any]:
        """Large payloads are made on the device from the seed."""
        import jax
        import jax.numpy as jnp

        shape = (self.p, v.count)

        def make(key):
            if v.dtype == "int32":
                bits = jax.random.bits(key, shape, jnp.uint32)
                return jax.lax.bitcast_convert_type(bits, jnp.int32)
            return jax.random.normal(key, shape, jnp.float32)

        fn = jax.jit(make, out_shardings=self.place)
        base = jax.random.fold_in(jax_key(self.seed), vi)
        return [fn(jax.random.fold_in(base, k)) for k in range(k_in)]

    # ------------------------------------------------------------- window

    def counters(self) -> Dict[str, Any]:
        t = self.engine.telemetry
        out = {"engine": {"compiles": t.compiles, "hits": t.hits,
                          "misses": t.misses, "dispatches": t.dispatches}}
        if self.svc is not None:
            s = self.svc.telemetry.snapshot()
            out["service"] = {k: s[k] for k in ("fused_requests", "fused_dispatches")}
        return out

    def window(self) -> Window:
        if self.entry == "service":
            return self._open_loop()
        return self._closed_loop()

    def _open_loop(self) -> Window:
        n = len(self.due)
        late = np.zeros(n)
        t0 = time.perf_counter()
        hard = t0 + self.seconds + LATE_S
        stop = threading.Event()
        collector = threading.Thread(target=self._collect, args=(hard, stop), daemon=True)
        collector.start()
        for k in range(n):
            due = t0 + self.due[k]
            wait = due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            now = time.perf_counter()
            late[k] = now - due
            try:
                ticket = self.clients[int(self.tenant[k])].submit(
                    self.words[k], self.dev_x[k])
            except Exception as e:  # noqa: BLE001 - refused: never answered
                self.errors[k] = repr(e)
                continue
            self.submitted.append((k, ticket))
        stop.set()
        collector.join(max(0.0, hard - time.perf_counter()) + 1.0)
        close = t0 + self.seconds
        done = self.done_t
        lat = done - (t0 + self.due)
        answered = np.isfinite(done)
        end = float(np.nanmax(done)) if answered.any() else close
        win = Window(start=t0, end=max(close, end))
        win.latencies_s = lat[answered].tolist()
        win.attempted = n
        win.failed = int(n - answered.sum())
        self.in_window = answered & (done <= close)
        counts = np.bincount(self.req_shape, minlength=len(self.descs))
        win.ops = [(d, int(c)) for d, c in zip(self.descs, counts)]
        win.info = {
            "setup_phases_s": self.setup_phases,
            "generator_late_us": {
                "p50": float(np.percentile(late, 50) * 1e6) if n else 0.0,
                "p99": float(np.percentile(late, 99) * 1e6) if n else 0.0,
                "max": float(late.max() * 1e6) if n else 0.0,
            },
            "offered_req_per_s": self.rate,
            "answered_by_close": int(self.in_window.sum()),
            "backlog_at_close": int(n - self.in_window.sum() - int((self.due > self.seconds).sum())),
            "latency_p50_us_first_third": _third_p50(lat, self.due, self.seconds, 0),
            "latency_p50_us_last_third": _third_p50(lat, self.due, self.seconds, 2),
        }
        self.window_ = win
        return win

    def _closed_loop(self) -> Window:
        jax = self._jax
        eng = self.engine
        nv = len(self.descs)
        k_in = len(self.inputs[0])
        keep = int(self.workload["check_sample"])
        rng = traffic.rng_for(self.seed, 4)
        u = rng.random(1 << 16)
        sample: List[Tuple[int, int, int, Any]] = []
        lat: List[float] = []
        counts = [0] * nv
        i = 0
        t0 = time.perf_counter()
        t_end = t0 + self.seconds
        te = t0
        while te < t_end:
            vi = i % nv
            ki = (i // nv) % k_in
            ts = time.perf_counter()
            out = eng.offload(self.descs[vi], self.inputs[vi][ki], **self.offload_kw)
            jax.block_until_ready(out)
            te = time.perf_counter()
            lat.append(te - ts)
            counts[vi] += 1
            # reservoir sample of the window's answers, drawn from the seed
            if i < keep:
                sample.append((i, vi, ki, out))
            else:
                if i % len(u) == 0:
                    u = rng.random(len(u))
                j = int(u[i % len(u)] * (i + 1))
                if j < keep:
                    sample[j] = (i, vi, ki, out)
            del out
            i += 1
        win = Window(start=t0, end=te, latencies_s=lat, attempted=i)
        win.info = {"setup_phases_s": self.setup_phases}
        win.ops = [(d, c) for d, c in zip(self.descs, counts)]
        self.sample = sample
        self.window_ = win
        return win

    def spans(self) -> Optional[list]:
        return window_spans(self.window_)

    # ------------------------------------------------------------- check

    def release(self) -> None:
        """Stop the program and bring the answers to compare to the host."""
        if self.entry == "service":
            self.answers = {
                k: np.asarray(r) for k, r in enumerate(self.results) if r is not None
            }
            self.results = []
            self.dev_x = []
            for c in self.clients:
                c.close()
            self.svc.stop()
        else:
            self.answers = {}
            for i, vi, ki, out in self.sample:
                self.answers[i] = (vi, ki, np.asarray(out))
                if (vi, ki) not in self.host_inputs:
                    self.host_inputs[(vi, ki)] = np.asarray(self.inputs[vi][ki])
            self.sample = []
            self.inputs = []

    def check(self, control: bool = False) -> List[Check]:
        """Bit-for-bit comparison with the reference. With ``control`` the
        reference in the next narrower type stands in the program's place."""
        wrong = 0
        win = self.window_
        if self.entry == "service":
            good = 0
            for k, got in self.answers.items():
                s = self.shapes[int(self.tenant[k])]
                x = self.host_x[k]
                want = reference.collective(s.coll, s.op, x)
                if control:
                    got = reference.control(s.coll, s.op, x)
                ok = got.shape == want.shape and np.array_equal(got, want)
                wrong += not ok
                good += bool(ok and self.in_window[k])
            win.correct_in_window = good
            compared = len(self.answers)
            missing = win.failed
        else:
            for i, (vi, ki, got) in self.answers.items():
                v = self.variants[vi]
                x = self.host_inputs[(vi, ki)]
                want = reference.collective(v.coll, v.op, x)
                if control:
                    got = reference.control(v.coll, v.op, x)
                wrong += not (got.shape == want.shape and np.array_equal(got, want))
            win.correct_in_window = win.attempted
            compared = len(self.answers)
            missing = 0
        win.info["answers_compared"] = compared
        return [
            Check("wrong_answers", wrong, 0),
            Check("missing_answers", missing, 0),
        ]


def _third_p50(lat: np.ndarray, due: np.ndarray, seconds: float, third: int) -> Optional[float]:
    sel = (due >= third * seconds / 3) & (due < (third + 1) * seconds / 3) & np.isfinite(lat)
    return float(np.median(lat[sel]) * 1e6) if sel.any() else None


Cell = OffloadCell

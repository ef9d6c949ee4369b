"""The driver-mode offload cell (``osu_scan.ici4``) on four virtual CPU
devices, at the cell's own sizes, through the harness's whole run but the
look for a chip: the program, the control, and two faults planted in the
timed path (an answer altered where it is produced, the exchange between
chips left out). Prints one JSON line per case.

    python chipbench/tests/driver_check.py

The device count has to be set before JAX is imported, so this runs in a
process of its own (``test_chipbench_driver.py`` starts it).
"""

import json
import os
import sys
import time
from pathlib import Path

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=4 " + os.environ.get("XLA_FLAGS", "")
)
os.environ["JAX_PLATFORMS"] = "cpu"
HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1]), str(HERE.parents[1] / "src")]

import jax  # noqa: E402

from chipbench import harness, peaks, spec  # noqa: E402
from test_chipbench_control import _altered, _no_exchange  # noqa: E402

CELL = "osu_scan.ici4"
SEED = 2**33 + 17
FAULTS = {"altered": _altered, "no_exchange": _no_exchange}


def run(control: bool = False):
    bench = spec.load_benchmark()
    return harness.run_cell(
        bench, spec.cell(bench, CELL), seed=SEED, seconds=1.0, trace=False,
        devices=jax.devices()[:4], t_start=time.perf_counter(),
        peaks_of=lambda kind: peaks.PEAKS["TPU v5 lite"], control=control,
    )


def main() -> None:
    from repro.offload.engine import OffloadEngine

    cases = {"program": lambda: run(), "control": lambda: run(control=True)}
    for name, fault in FAULTS.items():
        def faulty(fault=fault):
            orig = OffloadEngine.offload
            OffloadEngine.offload = fault(orig)
            try:
                return run()
            finally:
                OffloadEngine.offload = orig
        cases[name] = faulty
    for name, case in cases.items():
        out = case()
        print(json.dumps({"case": name, "correct": out["correct"],
                          "attempted": out["attempted"], "checks": out["checks"],
                          "device_count": out["device"]["count"]}), flush=True)


if __name__ == "__main__":
    main()

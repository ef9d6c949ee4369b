"""The driver-mode offload cell on a four-device CPU mesh: the program
reads correct, and the control and each planted fault read not correct
(``driver_check.py``, in a process of its own)."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent


@pytest.fixture(scope="module")
def cases():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, str(HERE / "driver_check.py")], capture_output=True,
        text=True, timeout=600, env=env, cwd=str(HERE.parents[1]),
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    lines = [json.loads(l) for l in proc.stdout.splitlines() if l.startswith('{"case"')]
    return {c["case"]: c for c in lines}


def test_driver_mode_program_is_correct_on_four_devices(cases):
    c = cases["program"]
    assert c["correct"] and c["device_count"] == 4 and c["attempted"] > 0
    assert c["checks"]["wrong_answers"]["value"] == 0
    assert c["checks"]["missing_answers"]["value"] == 0


@pytest.mark.parametrize("case", ["control", "altered", "no_exchange"])
def test_driver_mode_control_and_faults_are_not_correct(cases, case):
    c = cases[case]
    assert not c["correct"] and c["checks"]["wrong_answers"]["value"] > 0

"""Chip benchmark of the collective-offload system.

``python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` on the TPU it is started
on and prints one JSON result line. Everything that measures lives here:
traffic and payload generators (``traffic``), the plain references
(``reference``, ``mamba_ref``), the device-trace reduction
(``tracereduce``), the peaks table and work functions (``peaks``), and one
reader per per-layer metric (``metrics/<name>.py``). Configurations
(``configs/<name>.json``) and traffic mixes (``workloads/<cell>.json``) are
data files found by name; the program under test is driven only through its
public entry points, by the system module the configuration names
(``systems/<system>.py``), and a training configuration names its
yardstick (``<reference>.py``) the same way (``spec`` says what each
exports).
"""

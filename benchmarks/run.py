"""Benchmark harness entrypoint: one function per paper table/figure.

  fig4/fig5 (scan_latency)      -- host-visible SW vs offloaded scan latency
  fig6/fig7 (offloaded_latency) -- in-network latency per algorithm + the
                                   derived ICI model + selector crossovers
  tuned_vs_static               -- autotuner crossover report + engine smoke
                                   + planned-collective sections: tuned vs
                                   fixed axis splits and the 3D planner
                                   cache-hit proof
  service_throughput            -- multi-tenant broker requests/sec and
                                   p50/p99 latency vs client count, with
                                   coalescing on/off
  fusion_speedup                -- tuned schedule grid (fused x chunked
                                   streaming) vs raw planned collectives:
                                   communication rounds + measured us +
                                   bitwise proof + chunking check +
                                   profiler-sourced device latency
  roofline (report)             -- dry-run derived roofline tables

Prints ``name,...,derived`` CSV sections. Run:
  PYTHONPATH=src python -m benchmarks.run [--quick | --smoke]
                                          [--report-json [PATH]]

``--smoke`` runs only the offload-engine smoke (budgeted tuning grid +
descriptor-cache proof + one 3D planned collective end-to-end with an
asserted schedule-cache hit rate + the service broker's
coalesce/bitwise proof + the plan-optimizer's fused-vs-unfused rounds/
bitwise/device-latency proof) — the CI regression gate for the offload
subsystem.

``--report-json`` writes the service-throughput stats to a JSON artifact
(default ``BENCH_service.json`` next to this file) and the fusion stats to
``BENCH_fusion.json`` for the perf trajectory.
"""

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmarks import (  # noqa: E402
    fusion_speedup,
    offloaded_latency,
    report,
    scan_latency,
    service_throughput,
    tuned_vs_static,
)

DEFAULT_REPORT_PATH = Path(__file__).resolve().parent / "BENCH_service.json"


def _write_report(path: Path, stats, mode: str) -> None:
    payload = {
        "benchmark": "service_throughput",
        "mode": mode,
        "columns": "one dict per (clients, coalesce) configuration",
        "stats": stats,
    }
    path.write_text(json.dumps(payload, indent=2) + "\n")
    print(f"# service throughput stats written to {path}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="fewer timing iters")
    ap.add_argument(
        "--smoke",
        action="store_true",
        help="offload-engine smoke benchmark only (~10 s)",
    )
    ap.add_argument(
        "--report-json",
        nargs="?",
        const=str(DEFAULT_REPORT_PATH),
        default=None,
        metavar="PATH",
        help="write service-throughput stats to a JSON artifact "
        f"(default {DEFAULT_REPORT_PATH.name})",
    )
    args = ap.parse_args()
    iters = 8 if args.quick else 30
    service_stats: list = []

    if args.smoke:
        print(
            "# === Offload engine smoke: tuned-vs-static + planned-3D "
            "cache proof ==="
        )
        for row in tuned_vs_static.smoke():
            print(row)
        print()
        print(
            "# === Service smoke: multi-tenant broker, coalesced vs "
            "direct (bitwise) ==="
        )
        print(
            "service_throughput,clients,coalesce,requests,reqs_per_s,"
            "p50_us,p99_us,mean_us,coalesce_factor"
        )
        for row in service_throughput.smoke(stats_out=service_stats):
            print(row)
        print()
        print(
            "# === Fusion smoke: tuned schedule grid vs raw "
            "(rounds + bitwise + chunked streaming + device latency) ==="
        )
        print(
            "fusion_speedup,coll,sizes,msg_bytes,raw_rounds,fused_rounds,"
            "raw_us,fused_us,speedup,bitwise,tuned_opt,tuned_chunks"
        )
        fusion_stats: list = []
        for row in fusion_speedup.smoke(stats_out=fusion_stats):
            print(row)
        if args.report_json:
            _write_report(Path(args.report_json), service_stats, "smoke")
            fusion_speedup.write_report(
                fusion_speedup.DEFAULT_REPORT_PATH, fusion_stats, "smoke"
            )
        return

    print("# === Paper Fig. 4/5: host-visible scan latency (8 ranks) ===")
    print("figure,algo,variant,msg_bytes,us_per_call")
    for row in scan_latency.run(iters=iters):
        print(row)
    for row in scan_latency.run_min(iters=iters):
        print(row)

    print()
    print("# === Paper Fig. 6/7: offloaded in-network latency ===")
    print("figure,algo,metric,msg_bytes,value_us")
    for row in offloaded_latency.run():
        print(row)
    for row in offloaded_latency.selector_crossover():
        print(row)

    print()
    print("# === Tuned-vs-static selection crossovers (autotuner) ===")
    print(
        "section,coll,p,msg_bytes,static_algo,tuned_algo,"
        "static_meas_us,tuned_meas_us,changed"
    )
    for row in tuned_vs_static.run(
        iters=max(3, iters // 6), time_budget_s=120.0
    ):
        print(row)
    for row in tuned_vs_static.engine_smoke():
        print(row)

    print()
    print("# === Planned collectives: tuned vs fixed axis split + 3D ===")
    print(
        "section,coll,sizes,msg_bytes,fixed_order,fixed_us,tuned_order,"
        "tuned_us,speedup"
    )
    for row in tuned_vs_static.split_report(
        topologies=((2, 4), (4, 2), (2, 8), (2, 2, 2), (2, 2, 4)),
        payloads=(1024, 65536),
        colls=("scan", "allreduce"),
        iters=max(3, iters // 6),
        time_budget_s=120.0,
    ):
        print(row)
    for row in tuned_vs_static.planned_smoke():
        print(row)


    print()
    print("# === Service throughput: multi-tenant broker, coalesce on/off ===")
    print(
        "service_throughput,clients,coalesce,requests,reqs_per_s,"
        "p50_us,p99_us,mean_us,coalesce_factor"
    )
    for row in service_throughput.run(
        client_counts=(1, 2, 4) if args.quick else (1, 2, 4, 8),
        n_requests=8 if args.quick else 32,
        stats_out=service_stats,
    ):
        print(row)
    if args.report_json:
        _write_report(Path(args.report_json), service_stats, "full")

    print()
    print("# === Fusion speedup: tuned schedule grid vs raw ===")
    print(
        "fusion_speedup,coll,sizes,msg_bytes,raw_rounds,fused_rounds,"
        "raw_us,fused_us,speedup,bitwise,tuned_opt,tuned_chunks"
    )
    fusion_stats: list = []
    for row in fusion_speedup.run(
        iters=3 if args.quick else 5, stats_out=fusion_stats
    ):
        print(row)
    if args.report_json:
        fusion_speedup.write_report(
            fusion_speedup.DEFAULT_REPORT_PATH, fusion_stats, "full"
        )

    print()
    print("# === Roofline tables (from dry-run artifacts) ===")
    try:
        report.main()
    except Exception as e:  # artifacts may be absent on a fresh clone
        print(f"(roofline artifacts missing: {e}; run repro.launch.dryrun --all)")


if __name__ == "__main__":
    main()

"""Benchmark driver: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.

  fig6   CPU-usage prediction accuracy            (bench_prediction)
  fig7   instance-count selection (RollingCount / UniqueVisitor)
  fig8   throughput: default vs proposed vs optimal (also fig3)
  fig9   per-machine utilization comparison
  fig10  large-scale simulation scenarios + Table 4/5
  sec3   scheduler wall-time vs exhaustive optimal
  refine refine/optimal engine baseline (writes BENCH_refine.json)
  dispatch closed-form scorer backend crossover (writes BENCH_dispatch.json)
  runtime online streaming runtime: static vs online controller vs oracle
         on drift scenarios (writes BENCH_runtime.json)
  multitenant 100-tenant fairness scale, tenant-batched scoring, shared
         runtime (writes BENCH_multitenant.json)
  netaware network-aware vs distance-blind placement on rack-structured
         clusters (writes BENCH_netaware.json)
"""

from __future__ import annotations

from repro.compile_cache import setup_compile_cache

from benchmarks import (
    bench_dispatch,
    bench_instances,
    bench_largescale,
    bench_multitenant,
    bench_netaware,
    bench_prediction,
    bench_refine,
    bench_runtime,
    bench_sched_speed,
    bench_throughput,
    bench_utilization,
)


def main() -> None:
    setup_compile_cache()
    print("name,us_per_call,derived")
    bench_prediction.main()
    bench_throughput.main()
    bench_instances.main()
    bench_utilization.main()
    bench_largescale.main()
    bench_sched_speed.main(json_path="BENCH_sched.json")
    bench_refine.main(json_path="BENCH_refine.json")
    bench_dispatch.main(json_path="BENCH_dispatch.json")
    bench_runtime.main(json_path="BENCH_runtime.json")
    bench_multitenant.main(json_path="BENCH_multitenant.json")
    bench_netaware.main(json_path="BENCH_netaware.json")


if __name__ == "__main__":
    main()

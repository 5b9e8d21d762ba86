"""Benchmark harness — one entry per paper table/figure.

  Fig. 1b  bench_barrier   barrier crossing latency
  Fig. 4   bench_lock      single-lock + transactional locking vs MPI-style
  Fig. 5   bench_kvstore   kv throughput × mix × distribution × window
                           × implementation (hash vs reference)
  §9       bench_stream    windowed queue/ringbuffer vs scalar references,
                           ReplicatedLog append+sync latency/lag/bytes
  §10      bench_locality  skewed-reader placement: wire bytes before/after
                           rebalance(), migration transparency + replication
  §14/§15  bench_crossover one-sided vs active-message vs pallas backend
                           crossover: modeled bytes/rounds/cost × width
                           × skew × mix (three-way strict wins)
  Fig. 7   bench_power     DC/DC control-loop stability vs period
  §Roofline bench_roofline dry-run-derived roofline table (reads reports/)
                           + §15.3 DMA measured-vs-modeled agreement gates

Prints ``name,us_per_call,derived`` CSV rows; the kvstore and lock
benchmarks additionally persist machine-readable rows (variant, us,
ops/s, modeled wire bytes, hit-rate/speedup columns) to
``BENCH_kvstore.json`` / ``BENCH_lock.json`` at the repo root so the perf
trajectory is tracked across PRs (CI uploads both as artifacts).

Usage: PYTHONPATH=src python -m benchmarks.run [--only barrier,lock,...]
                                               [--smoke] [--json-dir DIR]
"""
from __future__ import annotations

import argparse
import os
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="comma list: barrier,lock,kvstore,stream,"
                         "locality,failover,crossover,power,roofline")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny configs for CI smoke runs")
    ap.add_argument("--json-dir", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))),
        help="where BENCH_*.json files land (default: repo root)")
    args = ap.parse_args()
    want = set(args.only.split(",")) if args.only else None

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

    from .common import BenchJson, Csv
    csv = Csv()
    print("name,us_per_call,derived")

    def enabled(name):
        return want is None or name in want

    if enabled("barrier"):
        from . import bench_barrier
        bench_barrier.run(csv)
    if enabled("lock"):
        from . import bench_lock
        jt = BenchJson()
        bench_lock.run(csv, rounds=4 if args.smoke else 12, jt=jt)
        path = jt.dump(os.path.join(args.json_dir, "BENCH_lock.json"))
        print(f"# wrote {path} ({len(jt.rows)} rows)", file=sys.stderr)
    if enabled("kvstore"):
        from . import bench_kvstore
        jt = BenchJson()
        bench_kvstore.run(csv, rounds=2 if args.smoke else 8, jt=jt,
                          smoke=args.smoke)
        path = jt.dump(os.path.join(args.json_dir, "BENCH_kvstore.json"))
        print(f"# wrote {path} ({len(jt.rows)} rows)", file=sys.stderr)
    if enabled("stream"):
        from . import bench_stream
        jt = BenchJson()
        bench_stream.run(csv, rounds=2 if args.smoke else 8, jt=jt,
                         smoke=args.smoke)
        path = jt.dump(os.path.join(args.json_dir, "BENCH_stream.json"))
        print(f"# wrote {path} ({len(jt.rows)} rows)", file=sys.stderr)
    if enabled("locality"):
        from . import bench_locality
        jt = BenchJson()
        bench_locality.run(csv, rounds=2 if args.smoke else 8, jt=jt,
                           smoke=args.smoke)
        path = jt.dump(os.path.join(args.json_dir, "BENCH_locality.json"))
        print(f"# wrote {path} ({len(jt.rows)} rows)", file=sys.stderr)
    if enabled("failover"):
        from . import bench_failover
        jt = BenchJson()
        bench_failover.run(csv, rounds=2 if args.smoke else 8, jt=jt,
                           smoke=args.smoke)
        path = jt.dump(os.path.join(args.json_dir, "BENCH_failover.json"))
        print(f"# wrote {path} ({len(jt.rows)} rows)", file=sys.stderr)
    if enabled("crossover"):
        from . import bench_crossover
        jt = BenchJson()
        bench_crossover.run(csv, rounds=2 if args.smoke else 6, jt=jt,
                            smoke=args.smoke)
        path = jt.dump(os.path.join(args.json_dir, "BENCH_crossover.json"))
        print(f"# wrote {path} ({len(jt.rows)} rows)", file=sys.stderr)
    if enabled("power"):
        from . import bench_power
        bench_power.run(csv)
    if enabled("roofline"):
        from . import bench_roofline
        bench_roofline.run(csv, smoke=args.smoke)
    print(f"# {len(csv.rows)} rows", file=sys.stderr)


if __name__ == "__main__":
    main()

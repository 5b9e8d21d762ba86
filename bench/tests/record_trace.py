"""Record the small chip trace that ``test_trace.py`` reduces.

Run on the chip, from the checkout root:

    python bench/tests/record_trace.py --workload ycsb_a.p8 \
        --out bench/tests/data/ycsb_a_p8_tiny.xplane.pb

It builds the cell's store at a tiny size (64 slots per node, 8 lanes per
participant), loads it and traces a few closed-loop windows with the
benchmark's own host spans, then copies the ``.xplane.pb`` to ``--out``
and prints the trace's planes and lines, and what ``trace.reduce`` reads.
"""
from __future__ import annotations

import argparse
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
WINDOWS = 4
SLOTS = 64
LANES = 8


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax
    from jax.profiler import ProfileData, TraceAnnotation

    from bench import run, trace, traffic
    from bench.store import Store

    cell, cfg, mix, entry_mod, _, _ = run.load_cell(args.workload)
    cfg = {**cfg, "slots_per_node": SLOTS,
           "index_capacity": 2 * cfg["participants"] * SLOTS}
    mix = {**mix, "lanes_per_participant": LANES}
    store = Store(cfg, LANES, jax.devices())
    store.compile()
    seed_d = store.seed_arg(args.seed)
    entry = entry_mod.Entry(store, seed_d)
    gen = traffic.Traffic(mix, store.P, int(store.P * SLOTS * 0.8),
                          args.seed)
    state, _ = store.load(store.init(), gen.load_windows(), seed_d)
    state, out = entry.dispatch(state, gen.next_window())
    entry.fetch(out)
    log_dir = tempfile.mkdtemp(prefix="bench_trace_")
    jax.profiler.start_trace(log_dir)
    for _ in range(WINDOWS):
        with TraceAnnotation("make_inputs"):
            win = gen.next_window()
        with TraceAnnotation("dispatch"):
            state, out = entry.dispatch(state, win)
        with TraceAnnotation("wait"):
            entry.fetch(out)
    jax.profiler.stop_trace()
    path = trace.find_xplane(log_dir)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    shutil.copyfile(path, args.out)
    shutil.rmtree(log_dir, ignore_errors=True)

    for plane in ProfileData.from_file(args.out).planes:
        print(f"plane {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            print(f"  line {line.name!r}: {len(evs)} events")
            for ev in evs[:3]:
                print(f"    {ev.name} start {ev.start_ns} dur "
                      f"{ev.duration_ns} stats {list(ev.stats)[:6]}")
    print("reduced", trace.reduce(args.out))
    print(f"wrote {args.out} ({os.path.getsize(args.out)} bytes)")
    return 0


if __name__ == "__main__":
    sys.exit(main())

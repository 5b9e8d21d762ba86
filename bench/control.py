"""The control of the comparison that decides ``correct``.

The plain reference is put in the program's place with one guarantee of
the configuration broken: linearizability.  Its GETs read a copy of the
store that lags one window behind, as a read replica updated
asynchronously would, or a pipeline that sends the next window before
the last one's writes have landed.  Its answers go through the same
comparison as a run's, and the control has to come out as not correct.

    python bench/control.py --workload ycsb_a.p8 --seeds 11 12 13 --windows 400

It makes the cell's load and windows from each seed at the cell's size,
as a run would, and prints each number compared beside its limit, one
JSON line per seed.  It uses no device.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class StaleReadStore:
    """The reference, with GETs served from the state one window old."""

    def __init__(self, n_keys: int, seed: int, width: int):
        from bench.reference import SequentialStore
        self.now = SequentialStore(n_keys, seed, width)
        self.lagging = SequentialStore(n_keys, seed, width)
        self.last = None

    def answer(self, ops, keys, versions):
        from bench.reference import GET
        found, _ = self.now.answer(ops, keys, versions)
        gets = np.ravel(ops) == GET
        # the copy holds every window but the last one
        stale_found, stale_values = self.lagging.answer(
            np.where(gets, GET, 0), keys, versions)
        if self.last is not None:
            self.lagging.answer(*self.last)
        found = np.where(gets, stale_found, found)
        self.last = (ops, keys, versions)
        return found, stale_values


def control(workload: str, seed: int, windows: int, *,
            cfg_overrides=None, mix_overrides=None) -> dict:
    """Counts of the comparison when the stale-read store answers
    ``windows`` query windows of the cell after its load."""
    from bench import run, traffic
    from bench.reference import NOP, SequentialStore

    _, cfg, mix, _, _, _ = run.load_cell(workload)
    cfg = {**cfg, **(cfg_overrides or {})}
    mix = {**mix, **(mix_overrides or {})}
    P, S, W = int(cfg["participants"]), int(cfg["slots_per_node"]), \
        int(cfg["value_width"])
    n_loaded = int(P * S * float(cfg["load_fraction"]))
    gen = traffic.Traffic(mix, P, n_loaded, seed)
    program = StaleReadStore(n_loaded, seed, W)
    ref = SequentialStore(n_loaded, seed, W)
    counts = {k: 0 for k in SequentialStore.COUNTS}
    for ops, keys, versions in gen.load_windows():
        program.answer(ops, keys, versions)
        ref.answer(ops, keys, versions)
    for _ in range(run.WARMUP_WINDOWS + windows):
        ops, keys, versions = gen.next_window()
        found, values = program.answer(ops, keys, versions)
        for k, v in ref.check(ops, keys, versions, found, values).items():
            counts[k] += v
    checks = {k: {"value": v, "limit": run.LIMIT} for k, v in counts.items()}
    return {"correct": all(v <= run.LIMIT for v in counts.values()),
            "seed": seed, "windows": windows,
            "lanes": int(np.count_nonzero(ops != NOP)) * windows,
            "checks": checks}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--windows", type=int, required=True,
                    help="query windows, as many as a run of the cell makes")
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    for seed in args.seeds:
        print(json.dumps(control(args.workload, seed, args.windows)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Record a profiler trace of the window with the host sub-spans, and the
compiled window's scope map, for ``bench/scopes.py``.

Run on the chip, from the checkout root.  At a tiny size (64 slots per
node, 8 lanes per participant, 4 traced windows), for the tests:

    python bench/tests/record_scoped_trace.py --workload ycsb_a.p8 \
        --out bench/tests/data/ycsb_a_p8_scoped_tiny.xplane.pb
    python bench/tests/record_scoped_trace.py \
        --config bench/configs/ycsb_1kib_mesh4.json --traffic ycsb_a \
        --out bench/tests/data/ycsb_a_mesh4_scoped_tiny.xplane.pb

At the cell's own size, with ``--full``: a closed loop of ``--seconds``
whose first ``--trace-seconds`` (3) are traced; it prints the time per
scope and per span (``scopes.table``), the five slowest windows split by
host phase, and a JSON line with the per-layer metrics read from the
trace and its longest idle gaps.

Each window runs inside the benchmark's host spans ``make_inputs``,
``dispatch`` (``put``: the three inputs to the device, ``launch``) and
``wait`` (``ready``: until the answers are on the device, ``fetch``: the
copy to the host).  The window's ``{instruction: scope path}`` map is
written beside the trace as ``<stem>.scopes.json``; ``--hlo`` also
writes the compiled window's HLO text.
"""
from __future__ import annotations

import argparse
import hashlib
import importlib
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
WINDOWS = 4
SLOTS = 64
LANES = 8
WARMUP_WINDOWS = 2
TRACE_SECONDS = 3.0
SLOWEST = 5
PHASES = ("make_inputs", "put", "launch", "ready", "fetch")
METRICS = ("device_idle_pct", "window_device_ms", "probe_ms", "lock_ms",
           "get_ms", "schedule_ms", "service_ms", "verbs_ms", "unscoped_ms",
           "fetch_idle_ms")


def scopes_path(xplane: str) -> str:
    return xplane[:-len(".xplane.pb")] + ".scopes.json"


def cell_parts(args):
    """(config, traffic mix, entry module) of a cell, or of a config file
    and a traffic mix named directly."""
    from bench import run
    if args.workload:
        _, cfg, mix, entry_mod, _, _ = run.load_cell(args.workload)
        return cfg, mix, entry_mod
    with open(args.config) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, "bench", "traffic",
                           args.traffic + ".json")) as f:
        mix = json.load(f)
    return cfg, mix, importlib.import_module(f"bench.entries.{mix['entry']}")


def one_window(entry, gen, state):
    """Run one window inside the host spans; returns (state, window,
    perf_counter stamps at the edges of the five phases)."""
    import jax
    from jax.profiler import TraceAnnotation

    store = entry.store
    t = [time.perf_counter()]
    with TraceAnnotation("make_inputs"):
        win = gen.next_window()
    t.append(time.perf_counter())
    with TraceAnnotation("dispatch"):
        with TraceAnnotation("put"):
            args = [store.put(x) for x in win]
        t.append(time.perf_counter())
        with TraceAnnotation("launch"):
            state, res = entry.program(state, *args, entry.seed_d)
        t.append(time.perf_counter())
    with TraceAnnotation("wait"):
        with TraceAnnotation("ready"):
            out = jax.block_until_ready((res.found, res.value))
        t.append(time.perf_counter())
        with TraceAnnotation("fetch"):
            entry.fetch(out)
        t.append(time.perf_counter())
    return state, win, t


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    where = ap.add_mutually_exclusive_group(required=True)
    where.add_argument("--workload")
    where.add_argument("--config")
    ap.add_argument("--traffic", default="ycsb_a")
    ap.add_argument("--out", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--trace-seconds", type=float, default=TRACE_SECONDS)
    ap.add_argument("--hlo")
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax
    import numpy as np

    # JAX's persistent cache keys a program with its debug info stripped,
    # so a cache hit keeps the name scopes of whichever program wrote it:
    # compile afresh, so that the HLO holds this program's scopes
    jax.config.update("jax_enable_compilation_cache", False)

    from bench import scopes, trace, traffic
    from bench.reference import NOP
    from bench.store import Store

    cfg, mix, entry_mod = cell_parts(args)
    if not args.full:
        cfg = {**cfg, "slots_per_node": SLOTS,
               "index_capacity": 2 * cfg["participants"] * SLOTS}
        mix = {**mix, "lanes_per_participant": LANES}
    store = Store(cfg, mix["lanes_per_participant"], jax.devices())
    compiled = store.compile()
    hlo = compiled.as_text()
    op_scope = scopes.op_scopes(hlo)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(scopes_path(args.out), "w") as f:
        json.dump(op_scope, f, indent=0, sort_keys=True)
    if args.hlo:
        with open(args.hlo, "w") as f:
            f.write(hlo)
    seed_d = store.seed_arg(args.seed)
    entry = entry_mod.Entry(store, seed_d)
    n_loaded = int(store.P * int(cfg["slots_per_node"])
                   * float(cfg["load_fraction"]))
    gen = traffic.Traffic(mix, store.P, n_loaded, args.seed)
    state, _ = store.load(store.init(), gen.load_windows(), seed_d)
    for _ in range(WARMUP_WINDOWS):
        state, _, _ = one_window(entry, gen, state)

    log_dir = tempfile.mkdtemp(prefix="bench_trace_")
    stamps, ops = [], []
    jax.profiler.start_trace(log_dir)
    tracing, t0 = True, time.perf_counter()
    while True:
        state, win, t = one_window(entry, gen, state)
        stamps.append(t)
        ops.append(int(np.count_nonzero(win[0] != NOP)))
        if tracing and (len(stamps) == WINDOWS if not args.full
                        else t[-1] - t0 >= args.trace_seconds):
            jax.profiler.stop_trace()
            tracing, traced = False, len(stamps)
        if not tracing and (not args.full or t[-1] - t0 >= args.seconds):
            break
    path = trace.find_xplane(log_dir)
    shutil.copyfile(path, args.out)
    shutil.rmtree(log_dir, ignore_errors=True)

    reduced = trace.reduce(args.out)
    if reduced is None:
        print("error: the trace holds no device operation", file=sys.stderr)
        return 1
    reduced.update(scopes.reduce(args.out, op_scope))
    print(scopes.table(reduced, traced), file=sys.stderr)
    st = np.asarray(stamps)
    phase_ms = 1e3 * np.diff(st, axis=1)
    total_ms = 1e3 * (st[:, -1] - st[:, 0])
    print("slowest windows, ms: total " + " ".join(PHASES), file=sys.stderr)
    for i in np.argsort(-total_ms)[:SLOWEST]:
        print(f"window {i} {total_ms[i]:.3f} "
              + " ".join(f"{v:.3f}" for v in phase_ms[i]), file=sys.stderr)
    record = {"traced_windows": traced}
    metrics = {}
    for name in METRICS:
        reader = importlib.import_module(f"bench.metrics.{name}")
        metrics[name] = reader.read(record, reduced)
    ops = np.asarray(ops)
    seconds = st[:, -1] - st[:, 0]
    result = {
        "device": {"kind": jax.devices()[0].device_kind,
                   "count": len(jax.devices())},
        "windows": len(stamps), "traced_windows": traced,
        "ops_per_s_traced": float(ops[:traced].sum()
                                  / (st[traced - 1, -1] - st[0, 0])),
        "ops_per_s_untraced": (float(ops[traced:].sum()
                                     / (st[-1, -1] - st[traced, 0]))
                               if len(stamps) > traced else None),
        "window_ms_median": float(1e3 * np.median(seconds)),
        "phase_ms_median": dict(zip(PHASES, np.median(phase_ms, axis=0)
                                    .tolist())),
        "hlo_stripped_sha256": hashlib.sha256(
            scopes.strip_metadata(hlo).encode()).hexdigest(),
        "metrics": metrics,
        "busy_s": reduced["busy_s"], "window_s": reduced["window_s"],
        "scope_s": reduced["scope_s"],
        "gap_s_by_span": reduced["gap_s_by_span"],
        "idle_gaps": reduced["idle_gaps"][:SLOWEST],
    }
    print(f"wrote {args.out} ({os.path.getsize(args.out)} bytes)",
          file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

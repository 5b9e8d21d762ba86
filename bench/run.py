"""Run one cell of ``BENCHMARK.json`` once, on the chip.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up builds the cell's store on the device, loads it through the
cell's compiled window and warms up; then a closed loop dispatches one
window at a time for ``--seconds``, each after the previous one's answers
are back on the host.  Once the window has closed, every answer of every
window is compared with the plain reference (``reference.py``).  The
last line of standard output is the JSON result; the numbers compared,
each beside its limit, are the last lines of standard error and the last
key of the result.

``--trace 0`` reports the cell's end-to-end metrics.  ``--trace 1``
traces the first seconds of the window with the profiler and reports the
cell's per-layer metrics, read from the trace by ``metrics/<name>.py``.

The run exits non-zero and prints no result where JAX's first device is
not a TPU, where it finds fewer chips than the cell asks for, or where
the device kind is not in ``peaks.json``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: query windows run and checked in set-up before the timed window
WARMUP_WINDOWS = 2
#: seconds of the window the profiler traces in a --trace 1 run
TRACE_SECONDS = 3.0
#: every number compared has limit 0: the store is exact
LIMIT = 0


class NoChip(RuntimeError):
    """The run found no device it may measure on."""


class RunGuard(RuntimeError):
    """A rule of the measured window was broken."""


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    when set, else the fixed path ``<checkout>/.jax_cache``."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def read_spec(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_cell(name: str, root: str = ROOT):
    """(cell, config, traffic mix, entry module, end-to-end metrics,
    per-layer metrics) of one cell, each found by its name."""
    spec = read_spec(root)
    cells = {c["name"]: c for c in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no cell {name!r} in BENCHMARK.json")
    cell = cells[name]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    with open(os.path.join(root, cfg_entry["file"])) as f:
        cfg = json.load(f)
    with open(os.path.join(root, "bench", "traffic",
                           cell["traffic"] + ".json")) as f:
        mix = json.load(f)
    entry = importlib.import_module(f"bench.entries.{mix['entry']}")

    def mine(m):
        return name in m.get("workloads", [name])
    e2e = [m for m in spec["end_to_end"] if mine(m)]
    layers = [m for m in spec["per_layer"] if mine(m)]
    return cell, cfg, mix, entry, e2e, layers


def hlo_guard(compiled):
    """Refuse a timed program that calls back to the host."""
    text = compiled.as_text()
    bad = re.findall(r'custom_call_target="([^"]*callback[^"]*)"', text)
    if bad or "is_host_transfer=true" in text:
        raise RunGuard("the timed program holds a host callback or host "
                       f"transfer ({sorted(set(bad)) or 'host transfer'})")


class CompileWatch:
    """Counts traces and compiles while armed."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/core/compile/jaxpr_trace_duration")

    def __init__(self):
        import jax
        self.armed = False
        self.seen = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if self.armed and event in self.EVENTS:
            self.seen.append(event)


def peak_bytes(devices) -> dict:
    return {d: int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in devices}


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        need_tpu: bool = True, peaks_kind: str | None = None,
        cfg_overrides: dict | None = None,
        mix_overrides: dict | None = None) -> dict:
    """Run one cell once and return its result.  ``need_tpu=False``,
    ``peaks_kind`` and the overrides serve the CPU rehearsal at a tiny
    size; a measurement passes none of them."""
    cell, cfg, mix, entry_mod, e2e, layers = load_cell(workload)
    cfg = {**cfg, **(cfg_overrides or {})}
    mix = {**mix, **(mix_overrides or {})}
    enable_compile_cache()
    import jax
    import numpy as np

    from bench import roofline, traffic
    from bench.reference import NOP, SequentialStore
    from bench.store import Store

    devices = jax.devices()
    dev0 = devices[0]
    if need_tpu and dev0.platform != "tpu":
        raise NoChip(f"JAX found no TPU (first device platform is "
                     f"{dev0.platform!r}); the benchmark runs only on the "
                     "chip")
    chips = int(cell["chips"])
    if len(devices) < chips or int(cfg["chips"]) != chips:
        raise NoChip(f"cell {workload} needs {chips} chips, JAX found "
                     f"{len(devices)}")
    try:
        peak = roofline.peaks(peaks_kind or dev0.device_kind)
    except KeyError as e:
        raise NoChip(str(e)) from e
    watch = CompileWatch()

    # -- set-up: build, compile, load, warm up --------------------------------
    store = Store(cfg, mix["lanes_per_participant"], devices)
    P, S = store.P, int(cfg["slots_per_node"])
    n_loaded = int(P * S * float(cfg["load_fraction"]))
    if "recordcount" in cfg and not cfg_overrides \
            and int(cfg["recordcount"]) != n_loaded:
        raise ValueError(f"recordcount {cfg['recordcount']} is not "
                         f"{n_loaded} = participants x slots x load_fraction")
    store.compile()
    seed_d = store.seed_arg(seed)
    entry = entry_mod.Entry(store, seed_d)
    hlo_guard(entry.program)
    gen = traffic.Traffic(mix, P, n_loaded, seed)
    load = gen.load_windows()
    state = store.init()
    state, load_found = store.load(state, load, seed_d)

    windows, answers, latency = [], [], []
    for _ in range(WARMUP_WINDOWS):
        win = gen.next_window()
        state, out = entry.dispatch(state, win)
        windows.append(win)
        answers.append(entry.fetch(out))
    setup_s = time.perf_counter() - T_START

    # -- the measured window ---------------------------------------------------
    # Python's cyclic collector is off in the window, as timeit has it: a
    # full collection walks every object of the process, JAX's included,
    # and its pauses land on a few windows of a run but not of another
    from jax.profiler import TraceAnnotation
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    tracing, traced = False, 0
    gc.collect()
    gc.freeze()
    gc.disable()
    try:
        watch.armed = True
        if trace:
            jax.profiler.start_trace(trace_dir)
            tracing = True
        t0 = time.perf_counter()
        deadline = t0 + seconds
        t_done = t_prev = t0
        host_s = 0.0
        while t_done < deadline:
            with TraceAnnotation("make_inputs"):
                win = gen.next_window()
            t_send = time.perf_counter()
            with TraceAnnotation("dispatch"):
                state, out = entry.dispatch(state, win)
            with TraceAnnotation("wait"):
                ans = entry.fetch(out)
            t_done = time.perf_counter()
            host_s += t_send - t_prev
            t_prev = t_done
            windows.append(win)
            answers.append(ans)
            latency.append(t_done - t_send)
            if tracing:
                traced += 1
                if t_done - t0 >= TRACE_SECONDS:
                    jax.profiler.stop_trace()
                    tracing = False
        if tracing:
            jax.profiler.stop_trace()
        watch.armed = False
    finally:
        gc.enable()
        gc.unfreeze()
    if watch.seen:
        raise RunGuard(f"{len(watch.seen)} trace or compile events inside "
                       f"the measured window: {sorted(set(watch.seen))}")
    window_s = t_done - t0
    timed = windows[WARMUP_WINDOWS:]
    lat_ms = 1e3 * np.asarray(latency)
    print(f"window: {len(timed)} windows in {window_s:.6f} s, host time "
          f"between windows {host_s:.6f} s, latency ms median "
          f"{np.median(lat_ms):.3f} max {lat_ms.max():.3f}, windows over "
          f"twice the median {int(np.sum(lat_ms > 2 * np.median(lat_ms)))}",
          file=sys.stderr)

    # -- after the window: memory, then the reference ---------------------------
    peaks_by_dev = peak_bytes(store.devices)
    fullest = max(peaks_by_dev, key=peaks_by_dev.get)
    del state, out
    ref = SequentialStore(n_loaded, seed, store.W)
    counts = {"load_found_mismatch": 0,
              **{k: 0 for k in SequentialStore.COUNTS}}
    for (ops, keys, versions), found in zip(load, load_found):
        want, _ = ref.answer(ops, keys, versions)
        counts["load_found_mismatch"] += int(np.sum(
            (np.ravel(ops) != NOP) & (np.ravel(found) != want)))
    failed = 0
    for i, (win, ans) in enumerate(zip(windows, answers)):
        c = entry.check(ref, win, ans)
        for k, v in c.items():
            counts[k] += v
        if i >= WARMUP_WINDOWS:
            failed += sum(c.values())
    checks = {k: {"value": v, "limit": LIMIT} for k, v in counts.items()}
    correct = bool(timed) and all(v <= LIMIT for v in counts.values())

    n_ops = [int(np.count_nonzero(w[0] != NOP)) for w in timed]
    attempted = sum(n_ops)
    result_device = {"platform": dev0.platform, "kind": dev0.device_kind,
                     "count": len(devices),
                     "memory_peak_bytes": peaks_by_dev[fullest]}
    metrics = {}
    breakdown = None
    if not trace:
        home_chip = store.chip_of(gen.home[1:])
        fullest_chip = store.devices.index(fullest)
        user_bytes = 1000 * int(np.count_nonzero(home_chip == fullest_chip))
        values = {
            "ops_per_s": attempted / window_s,
            "op_p95_ms": 1e3 * float(np.percentile(
                np.repeat(latency, n_ops), 95)),
            "space_amp": peaks_by_dev[fullest] / user_bytes,
            "setup_s": setup_s,
        }
        for m in e2e:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        from bench import trace as trace_mod
        reduced = trace_mod.reduce(trace_mod.find_xplane(trace_dir))
        lane_chip = store.chip_of(np.arange(P))[:, None]
        least = 0.0
        for ops, keys, _ in timed[:traced]:
            hbm, wire = roofline.window_bytes(
                ops, store.chip_of(gen.home[keys]),
                np.broadcast_to(lane_chip, ops.shape))
            least += roofline.least_seconds(hbm, wire, peak, chips)
        record = {"traced_windows": traced, "traced_least_s": least,
                  "chips": chips}
        for m in layers:
            reader = importlib.import_module(f"bench.metrics.{m['name']}")
            v = reader.read(record, reduced)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if reduced:
            result_device["busy_s"] = reduced["busy_s"]
            result_device["window_s"] = reduced["window_s"]
            breakdown = {"device_ops": reduced["device_ops"],
                         "idle_gaps": reduced["idle_gaps"]}
        shutil.rmtree(trace_dir, ignore_errors=True)

    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": result_device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["windows"] = len(timed)
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        result = run(args.workload, args.seed, args.seconds,
                     bool(args.trace))
    except NoChip as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

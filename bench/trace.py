"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

The window is the span from the first to the last of the benchmark's own
host spans (``make_inputs``, ``dispatch``, ``wait``).  On each device
plane the operations of its ``XLA Ops`` line are merged into busy
intervals inside that window; busy time is averaged over the devices.
Collective time is the union of the intervals of collective operations
(all-gather, reduce-scatter, all-reduce, all-to-all, collective-permute),
on the ``XLA Ops`` and ``Async XLA Ops`` lines, also averaged over the
devices.  An operation is named by its HLO instruction (``copy.107``);
the top operations are ranked by self time, the part of an operation
that no operation nested in it on the same line covers (a ``while``
holds its body's operations).  Each gap between busy intervals on the
first device is labelled by the host span that holds its midpoint.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

HOST_SPANS = ("make_inputs", "dispatch", "wait")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
COLLECTIVE = re.compile(
    r"^(all-gather|reduce-scatter|all-reduce|all-to-all|collective-permute)")
TOP = 10


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, "
                                f"found {len(paths)}")
    return paths[0]


def _merge(intervals):
    """Sorted, disjoint union of (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _length(merged) -> float:
    return sum(e - s for s, e in merged)


def op_name(event_name: str) -> str:
    """The HLO instruction an event names: ``%copy.107 = s32[...] copy(...)``
    gives ``copy.107``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def _self_times(events):
    """{name: self time} of properly nested (start, end, name) events."""
    out = defaultdict(float)
    stack = []                          # [end, name, time covered by children]
    for s, e, name in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            _, n, covered = stack.pop()
            out[n] -= covered
        if stack:
            stack[-1][2] += min(e, stack[-1][0]) - s
        out[name] += e - s
        stack.append([e, name, 0])
    for _, n, covered in stack:
        out[n] -= covered
    return out


def _device_planes(profile):
    planes = [p for p in profile.planes if p.name.startswith("/device:")
              and any(ln.name == OPS_LINE for ln in p.lines)]
    return sorted(planes, key=lambda p: p.name)


def reduce(path: str) -> dict:
    """Reduce one trace file.  Times are in seconds.  Returns ``None``
    where the trace holds no device operation or no host span."""
    from jax.profiler import ProfileData

    profile = ProfileData.from_file(path)
    spans = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in HOST_SPANS:
                    spans.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                  ev.name))
    devices = _device_planes(profile)
    if not spans or not devices:
        return None
    lo = min(s for s, _, _ in spans)
    hi = max(e for _, e, _ in spans)

    busy, collective = [], []
    op_time = defaultdict(float)
    first_busy = None
    for plane in devices:
        ops, coll = [], []
        for line in plane.lines:
            if line.name not in (OPS_LINE, ASYNC_LINE):
                continue
            for ev in line.events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                if e <= lo or s >= hi:
                    continue
                s, e, name = max(s, lo), min(e, hi), op_name(ev.name)
                if line.name == OPS_LINE:
                    ops.append((s, e, name))
                if COLLECTIVE.match(name):
                    coll.append((s, e))
        for name, t in _self_times(ops).items():
            op_time[name] += t * 1e-9
        merged = _merge((s, e) for s, e, _ in ops)
        if first_busy is None:
            first_busy = merged
        busy.append(_length(merged) * 1e-9)
        collective.append(_length(_merge(coll)) * 1e-9)

    gaps = []
    edges = [[lo, lo]] + first_busy + [[hi, hi]]
    for (_, a), (b, _) in zip(edges, edges[1:]):
        if b > a:
            mid = (a + b) / 2
            label = next((n for s, e, n in spans if s <= mid < e), "other")
            gaps.append([label, (b - a) * 1e-9])
    gaps.sort(key=lambda g: -g[1])
    n_dev = len(devices)
    top = sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy) / n_dev,
        "busy_s_per_device": busy,
        "collective_s": sum(collective) / n_dev,
        "devices": n_dev,
        "device_ops": [[name, t / n_dev] for name, t in top],
        "idle_gaps": gaps[:TOP],
        "host_spans": len(spans),
    }

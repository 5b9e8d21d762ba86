"""Device time by name scope and host idle time by host span, from a
profiler trace of the window and the compiled window's HLO.

The program names its layers with ``jax.named_scope``: one ``kv.<phase>``
scope per phase of ``KVStore.op_window`` (``kv.tracker`` nested in
``kv.service``) and one ``verb.<name>`` scope per verb that moves data
between participants.  The compiled HLO keeps each scope in an
instruction's ``metadata={op_name="jit(f)/vmap(kv.service)/while/body/
kv.tracker/..."}``; the trace names each device event by the same
instruction (``trace.op_name``).  So ``op_scopes`` maps instructions to
their scopes, and ``reduce`` gives each scope the self time of its ops.

The top-level phases (``TOP``), ``bench.*`` scopes and ``unscoped``, which
holds the ops under none of them (window glue and ops XLA adds without
metadata), partition the busy time.  ``kv.tracker`` and each ``verb.*``
are inclusive and cut across that partition; ``verb.*`` holds the ops
under any verb once.

Idle time is the first device's: each stretch of it goes to the
innermost host span that covers it (``make_inputs``, ``dispatch`` with
``put`` and ``launch`` in it, ``wait`` with ``ready`` and ``fetch``), or
to ``other`` where none does.
"""
from __future__ import annotations

import re
from bisect import bisect_left, bisect_right
from collections import defaultdict

from bench import trace

TOP = ("kv.probe", "kv.lock_acquire", "kv.plan", "kv.get", "kv.schedule",
       "kv.service", "kv.release")
SUB_SPANS = ("put", "launch", "ready", "fetch")
SPANS = trace.HOST_SPANS + SUB_SPANS
UNSCOPED = "unscoped"
VERBS = "verb.*"
OTHER = "other"
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_SCOPE = re.compile(r"(?:^|[/(])((?:kv|verb|bench)\.[A-Za-z0-9_]+)")
_COMP = re.compile(r"^(?:ENTRY\s+)?%?([^\s(]+)\s*\(.*\{\s*$")
_FUSES = re.compile(r"\bcalls=%?([^\s,{}]+)")
_RUNS = re.compile(r"\b(?:body|condition|true_computation|false_computation"
                   r")=(%?[^\s,{}]+)|\bbranch_computations=\{([^}]*)\}")
_STACK_SECTIONS = ("FileNames", "FunctionNames", "FileLocations",
                   "StackFrames")


def op_scopes(hlo_text: str) -> dict:
    """``{instruction: "scope/scope"}`` for every instruction of the HLO
    under a ``kv.*``, ``verb.*`` or ``bench.*`` scope, outermost first.

    An instruction's scopes are those of its own ``op_name``.  Where that
    holds none, as XLA leaves many fusions, they are those of the first
    scoped instruction of the computation it fuses, root first; else
    those of the control-flow op its computation runs in (the ops of a
    ``while`` body run in the ``while``).  Entry-level layout copies keep
    none."""
    comp_of, own, fuses, runs_in = {}, {}, {}, {}
    order = defaultdict(list)        # computation -> instructions, root first
    comp = None
    for line in hlo_text.splitlines():
        head = _COMP.match(line)
        if head:
            comp = head.group(1)
            continue
        m = _INSTR.match(line)
        if not m or comp is None:
            continue
        name = m.group(1)
        comp_of[name] = comp
        if m.group(0).lstrip().startswith("ROOT"):
            order[comp].insert(0, name)
        else:
            order[comp].append(name)
        n = _OP_NAME.search(line)
        own[name] = "/".join(dict.fromkeys(_SCOPE.findall(n.group(1)))) \
            if n else ""
        fuses[name] = _FUSES.findall(line)
        for one, many in _RUNS.findall(line):
            for c in (one or many).replace("%", "").split(","):
                runs_in[c.strip()] = name

    memo = {}

    def fused(c):
        for i in order[c]:
            p = own[i] or next(filter(None, map(fused, fuses[i])), "")
            if p:
                return p
        return ""

    def scope(name):
        if name not in memo:
            memo[name] = ""
            p = own[name] or next(filter(None, map(fused, fuses[name])), "")
            if not p and comp_of[name] in runs_in:
                p = scope(runs_in[comp_of[name]])
            memo[name] = p
        return memo[name]

    return {n: p for n in own if (p := scope(n))}


def strip_metadata(hlo_text: str) -> str:
    """The HLO text without ``metadata={...}`` and without the stack-frame
    tables: what is left is what the device runs."""
    out, skip = [], False
    for line in hlo_text.splitlines():
        if line in _STACK_SECTIONS:
            skip = True
        elif skip:
            skip = line != ""
        else:
            out.append(re.sub(r", metadata=\{[^{}]*\}", "", line))
    return "\n".join(out)


def partition_key(path: str) -> str:
    """The top-level phase or ``bench.*`` scope of a scope path, else
    ``unscoped``."""
    for name in path.split("/") if path else ():
        if name in TOP or name.startswith("bench."):
            return name
    return UNSCOPED


def _innermost(spans):
    """(cuts, labels): between ``cuts[i]`` and ``cuts[i+1]`` the innermost
    host span is ``labels[i]``, or ``other``.  The spans of one thread
    nest, so the innermost is the top of a stack of the open spans."""
    spans = sorted(((s, e, n) for s, e, n in spans if e > s),
                   key=lambda x: (x[0], -x[1]))
    cuts = sorted({t for s, e, _ in spans for t in (s, e)})
    labels, stack, j = [], [], 0
    for a in cuts[:-1]:
        while j < len(spans) and spans[j][0] <= a:
            stack.append(spans[j])
            j += 1
        while stack and stack[-1][1] <= a:
            stack.pop()
        labels.append(stack[-1][2] if stack else OTHER)
    return cuts, labels


def _idle_by_span(idle, spans):
    """{span: ns} of the (start, end) idle stretches, each split at the
    host spans' edges."""
    cuts, labels = _innermost(spans)
    out = defaultdict(float)
    for a, b in idle:
        inner = cuts[bisect_right(cuts, a):bisect_left(cuts, b)]
        points = [a, *inner, b]
        for u, v in zip(points, points[1:]):
            i = bisect_right(cuts, u) - 1
            out[labels[i] if 0 <= i < len(labels) else OTHER] += v - u
    return out


def reduce(path: str, op_scope: dict) -> dict:
    """``scope_s``: device self time of each scope, averaged over the
    devices, with every scope of ``op_scope`` listed (0.0 where it took
    none) and ``unscoped``; ``gap_s_by_span``: the first device's idle
    time by innermost host span, every span of the trace listed.  Times
    in seconds, inside the window ``trace.reduce`` reads.  ``None`` where
    the trace holds no device operation or no host span."""
    from jax.profiler import ProfileData

    profile = ProfileData.from_file(path)
    spans = []                  # the host lines that hold the window's spans
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                mine = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                        for ev in line.events if ev.name in SPANS]
                if any(n in trace.HOST_SPANS for _, _, n in mine):
                    spans += mine
    window = [(s, e) for s, e, n in spans if n in trace.HOST_SPANS]
    devices = trace._device_planes(profile)
    if not window or not devices:
        return None
    lo, hi = min(s for s, _ in window), max(e for _, e in window)

    scope_s = {UNSCOPED: 0.0}
    for path_ in op_scope.values():
        for name in path_.split("/"):
            scope_s[name] = 0.0
            if name.startswith("verb."):
                scope_s[VERBS] = 0.0
    first_busy = None
    for plane in devices:
        ops = []
        for line in plane.lines:
            if line.name != trace.OPS_LINE:
                continue
            for ev in line.events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                if e > lo and s < hi:
                    ops.append((max(s, lo), min(e, hi),
                                trace.op_name(ev.name)))
        for name, t in trace._self_times(ops).items():
            path_ = op_scope.get(name, "")
            names = set(path_.split("/")) - {""}
            names.add(partition_key(path_))
            if any(n.startswith("verb.") for n in names):
                names.add(VERBS)
            for n in names:
                scope_s[n] = scope_s.get(n, 0.0) + t * 1e-9
        if first_busy is None:
            first_busy = trace._merge((s, e) for s, e, _ in ops)
    n_dev = len(devices)

    edges = [[lo, lo]] + first_busy + [[hi, hi]]
    idle = [(a, b) for (_, a), (b, _) in zip(edges, edges[1:]) if b > a]
    gaps = {n: 0.0 for _, _, n in spans}
    for n, t in _idle_by_span(idle, spans).items():
        gaps[n] = gaps.get(n, 0.0) + t * 1e-9
    return {"scope_s": {n: t / n_dev for n, t in scope_s.items()},
            "gap_s_by_span": gaps}


def per_window_ms(record: dict, reduced: dict, key: str, names) -> float:
    """Milliseconds per traced window of ``reduced[key]`` summed over
    ``names``; ``None`` where the reduction lacks the key or every name,
    as for a trace reduced without scopes or sub-spans."""
    table = (reduced or {}).get(key)
    windows = record.get("traced_windows")
    got = [table[n] for n in names if n in table] if table else []
    if not got or not windows:
        return None
    return 1e3 * sum(got) / windows


def table(reduced: dict, windows: int) -> str:
    """Every scope's device ms and every span's idle ms per window."""
    rows = ["scope or span            ms per window"]
    for key, label in (("scope_s", "device"), ("gap_s_by_span", "idle")):
        for n, t in sorted(reduced[key].items(), key=lambda kv: -kv[1]):
            rows.append(f"{label} {n:<22} {1e3 * t / windows:12.6f}")
    return "\n".join(rows)


"""The window's name scopes and their reduction (``bench/scopes.py``).

The program names each phase of ``KVStore.op_window`` (``kv.*``) and each
verb that moves data between participants (``verb.*``); the compiled
HLO keeps the names in its metadata, and the benchmark's trace reduction
gives each scope the device time of its ops.  Here: the scope map of
synthetic HLO, the scopes of the tiny benchmark store's compiled window,
the per-layer metric readers, and the reduction of small traces recorded
on the chip (``bench/tests/record_scoped_trace.py``).
"""
import importlib
import json
import os
import sys

import jax
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import scopes, trace  # noqa: E402

DATA = os.path.join(ROOT, "bench", "tests", "data")
SCOPED = sorted(f for f in os.listdir(DATA)
                if f.endswith("_scoped_tiny.xplane.pb"))
READERS = ("fetch_idle_ms", "get_ms", "lock_ms", "probe_ms", "schedule_ms",
           "service_ms", "unscoped_ms", "verbs_ms")
LOCKED = {"kv.probe", "kv.lock_acquire", "kv.get", "kv.schedule",
          "kv.service", "kv.release"}

HLO = """\
HloModule jit_f, is_scheduled=true

FileNames
1 "kvstore.py"

%fused_computation.1 (param_0: s32[8,4]) -> s32[8,4] {
  %param_0 = s32[8,4]{1,0} parameter(0)
  %transpose.2 = s32[8,4]{1,0} transpose(%param_0), dimensions={0,1}
  ROOT %scatter.3 = s32[8,4]{1,0} scatter(%transpose.2), metadata={op_name="jit(f)/vmap(kv.service)/while/body/verb.write_batch/scatter" stack_frame_id=1}
}

%body.4 (arg.5: (s32[8,4])) -> (s32[8,4]) {
  %arg.5 = (s32[8,4]) parameter(0)
  %get-tuple-element.6 = s32[8,4]{1,0} get-tuple-element(%arg.5), index=0
  %fusion.7 = s32[8,4]{1,0} fusion(%get-tuple-element.6), kind=kLoop, calls=%fused_computation.1
  %add.8 = s32[8,4]{1,0} add(%fusion.7, %fusion.7), metadata={op_name="jit(f)/vmap(kv.service)/while/body/kv.tracker/add"}
  ROOT %tuple.9 = (s32[8,4]) tuple(%add.8)
}

%cond.10 (arg.11: (s32[8,4])) -> pred[] {
  %arg.11 = (s32[8,4]) parameter(0)
  ROOT %constant.12 = pred[] constant(false)
}

ENTRY %main.13 (st_idx.14: s32[8,4]) -> (s32[8,4]) {
  %st_idx.14 = s32[8,4]{0,1} parameter(0), metadata={op_name="st.idx"}
  %copy.15 = s32[8,4]{1,0} copy(%st_idx.14), metadata={op_name="st.idx"}
  %gather.16 = s32[8,4]{1,0} gather(%copy.15), metadata={op_name="jit(f)/shard_map(kv.probe)/gather"}
  %add.17 = s32[8,4]{1,0} add(%gather.16, %gather.16), metadata={op_name="jit(f)/vmap(bench.values)/add"}
  %tuple.18 = (s32[8,4]) tuple(%add.17)
  ROOT %while.19 = (s32[8,4]) while(%tuple.18), condition=%cond.10, body=%body.4, metadata={op_name="jit(f)/vmap(kv.service)/while"}
}
"""


@pytest.mark.parametrize("instruction,path", [
    ("gather.16", "kv.probe"),                      # under shard_map(...)
    ("add.17", "bench.values"),                     # under vmap(...)
    ("while.19", "kv.service"),
    ("add.8", "kv.service/kv.tracker"),             # nested, while/body/
    ("fusion.7", "kv.service/verb.write_batch"),    # from its fused root
    ("get-tuple-element.6", "kv.service"),          # runs in the while
    ("constant.12", "kv.service"),                  # in its condition
    ("copy.15", None),                              # an entry layout copy
    ("st_idx.14", None),
])
def test_op_scopes_of_synthetic_hlo(instruction, path):
    assert scopes.op_scopes(HLO).get(instruction) == path


@pytest.mark.parametrize("path,key", [
    ("kv.service/kv.tracker", "kv.service"),
    ("kv.service/verb.write_batch", "kv.service"),
    ("verb.gather_rows", scopes.UNSCOPED),
    ("bench.values", "bench.values"),
    ("", scopes.UNSCOPED),
])
def test_partition_key(path, key):
    assert scopes.partition_key(path) == key


def test_strip_metadata_leaves_the_ops():
    text = scopes.strip_metadata(HLO)
    assert "metadata" not in text and "FileNames" not in text
    assert text.count(" = ") == HLO.count(" = ")


@pytest.fixture(scope="module")
def window_hlo():
    """The compiled window of the tiny benchmark store on the CPU, on the
    locked path (the cell's) and on the lock-free path."""
    from bench import run
    from bench.store import Store

    _, cfg, mix, *_ = run.load_cell("ycsb_a.p8")
    cfg = {**cfg, "slots_per_node": 32,
           "index_capacity": 2 * cfg["participants"] * 32}
    out = {}
    for lockfree in (False, True):
        store = Store(cfg, 8, jax.devices())
        store.kv.lockfree = lockfree
        out[lockfree] = store.compile().as_text()
    return out


@pytest.mark.parametrize("lockfree", [False, True],
                         ids=["locked", "lockfree"])
def test_window_hlo_carries_every_phase(window_hlo, lockfree):
    names = set()
    for path in scopes.op_scopes(window_hlo[lockfree]).values():
        names.update(path.split("/"))
    want = LOCKED | {"kv.tracker"}
    if lockfree:                    # the plan takes the schedule's place
        want = want - {"kv.schedule"} | {"kv.plan"}
    assert want <= names
    assert any(n.startswith("verb.") for n in names)


def test_top_level_phases_do_not_nest(window_hlo):
    for text in window_hlo.values():
        for path in scopes.op_scopes(text).values():
            assert len(set(path.split("/")) & set(scopes.TOP)) <= 1, path


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_nothing_without_its_key(name):
    reader = importlib.import_module(f"bench.metrics.{name}")
    record = {"traced_windows": 4}
    plain = {"window_s": 1.0, "busy_s": 0.5, "devices": 1}
    assert reader.read(record, None) is None
    assert reader.read(record, plain) is None
    unscoped = {**plain, "scope_s": {scopes.UNSCOPED: 0.5},
                "gap_s_by_span": {"wait": 0.5}}
    assert reader.read(record, unscoped) is None


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_ms_per_window(name):
    reader = importlib.import_module(f"bench.metrics.{name}")
    scope_s = {n: 0.004 for n in scopes.TOP}
    scope_s.update({"kv.tracker": 0.002, scopes.VERBS: 0.001,
                    "verb.write_batch": 0.001, scopes.UNSCOPED: 0.008})
    reduced = {"scope_s": scope_s,
               "gap_s_by_span": {"fetch": 0.0, "ready": 0.002}}
    got = reader.read({"traced_windows": 4}, reduced)
    want = {"lock_ms": 2.0, "verbs_ms": 0.25, "unscoped_ms": 2.0,
            "fetch_idle_ms": 0.0}.get(name, 1.0)
    assert got == pytest.approx(want)


def test_recorded_scoped_traces_exist():
    assert {"ycsb_a_p8_scoped_tiny.xplane.pb",
            "ycsb_a_mesh4_scoped_tiny.xplane.pb"} <= set(SCOPED)


def _reduced(name):
    path = os.path.join(DATA, name)
    with open(path[:-len(".xplane.pb")] + ".scopes.json") as f:
        op_scope = json.load(f)
    return op_scope, trace.reduce(path), scopes.reduce(path, op_scope)


@pytest.mark.parametrize("name", SCOPED)
def test_scopes_partition_busy_time(name):
    op_scope, whole, red = _reduced(name)
    part = [t for n, t in red["scope_s"].items()
            if n in scopes.TOP or n.startswith("bench.")
            or n == scopes.UNSCOPED]
    assert sum(part) == pytest.approx(whole["busy_s"], abs=1e-9)
    ran = {n for p in op_scope.values() for n in p.split("/")
           if n.startswith("kv.")}
    assert ran >= LOCKED | {"kv.tracker"}
    assert all(red["scope_s"][n] > 0 for n in ran)
    assert red["scope_s"]["kv.tracker"] <= red["scope_s"]["kv.service"]
    assert 0 < red["scope_s"][scopes.VERBS] < whole["busy_s"]


@pytest.mark.parametrize("name", SCOPED)
def test_idle_time_by_span(name):
    _, whole, red = _reduced(name)
    gaps = red["gap_s_by_span"]
    assert set(scopes.SPANS) <= set(gaps) <= set(scopes.SPANS) | {"other"}
    idle = whole["window_s"] - whole["busy_s_per_device"][0]
    assert sum(gaps.values()) == pytest.approx(idle, abs=1e-9)
    assert gaps["fetch"] > 0        # the window is done while it copies


@pytest.mark.parametrize("name", sorted(
    f for f in os.listdir(DATA) if f.endswith(".xplane.pb")))
def test_trace_without_scope_map_is_all_unscoped(name):
    path = os.path.join(DATA, name)
    red = scopes.reduce(path, {})
    assert set(red["scope_s"]) == {scopes.UNSCOPED}
    assert red["scope_s"][scopes.UNSCOPED] == pytest.approx(
        trace.reduce(path)["busy_s"], abs=1e-9)

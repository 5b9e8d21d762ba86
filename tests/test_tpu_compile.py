"""Compile the main path for a described TPU v5e, with no chip attached.

The chip's compiler is installed with libtpu: it refuses what the chip
would refuse (Mosaic's tiling rules, VMEM and HBM capacity) although
nothing runs.  These tests compile the remote-DMA kernels and the
``chip_smoke.py`` store's window at deployment shapes, so a change that
would fail on the chip fails here first.

Only one process at a time may load libtpu, and every test worker
imports every test file: the topology is described inside a fixture, in
the worker that runs this file, never while a module is imported.
"""
import functools
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

from repro.kernels import remote_dma as rdma
from repro.launch import smoke

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
GiB = 1 << 30
V5E_HBM = 16 * GiB
S, W, N = 131072, smoke.VALUE_WIDTH + 3, 2048   # kvstore rows, P·B lanes


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    quiet = "TPU_LOG_DIR" not in os.environ   # else libtpu logs under /tmp
    if quiet:
        os.environ["TPU_LOG_DIR"] = "disabled"
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or it cannot describe a v5e here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        if quiet:
            del os.environ["TPU_LOG_DIR"]


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def chip_compiles(monkeypatch):
    """Lower the kernels for Mosaic (the CPU backend would pick interpret
    mode) and keep these compiles out of any persistent cache, which
    could not read them back without a chip."""
    monkeypatch.setattr(rdma, "_interpret", lambda: False)
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def _kernel_case(name, sds):
    if name == "build_descriptors":
        return (lambda t, i, e, w: rdma.build_descriptors(
            t, i, e, wire=w, op=rdma.OP_WRITE, row_nbytes=W * 4),
            [sds((N,))] * 4, ())
    if name == "gather_rows":
        return (rdma.gather_rows, [sds((S, W)), sds((N,)), sds((N,))], ())
    return (rdma.scatter_rows, [sds((S, W)), sds((N,)), sds((N, W)),
                                sds((N,)), sds((N,))], (0,))


@pytest.mark.parametrize("participants", [0, smoke.ONE_CHIP_P],
                         ids=["one", "vmap8"])
@pytest.mark.parametrize("kernel", ["build_descriptors", "gather_rows",
                                    "scatter_rows"])
def test_remote_dma_kernel_compiles_for_v5e(one_chip, kernel, participants):
    """Each kernel at the kvstore's row width (not a whole lane tile) and
    table size, alone and under the vmap participant binding."""
    lead = (participants,) if participants else ()

    def sds(shape):
        return jax.ShapeDtypeStruct(lead + shape, jnp.int32,
                                    sharding=one_chip)
    fn, args, donate = _kernel_case(kernel, sds)
    if participants:
        fn = jax.vmap(fn)
    compiled = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    ma = compiled.memory_analysis()
    if kernel == "scatter_rows":          # the table is updated in place
        assert ma.alias_size_in_bytes >= S * W * 4 * max(participants, 1)


def _window_lanes(store, sharding):
    return [jax.ShapeDtypeStruct((store.P, store.B), dt, sharding=sharding)
            for dt in (jnp.int32, jnp.uint32, jnp.uint32)]


@pytest.mark.parametrize("backend", ["onesided", "pallas"])
def test_smoke_window_fits_one_v5e(one_chip, backend):
    """The one-chip smoke's op_window, at its full size, compiles for one
    v5e and fits its HBM with the state donated."""
    store = smoke.build_store(smoke.ONE_CHIP_P, smoke.ONE_CHIP_SLOTS,
                              backend=backend)
    st = smoke.abstract_state(store, one_chip)
    compiled = store.window.lower(
        st, *_window_lanes(store, one_chip)).compile()
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes >= smoke.state_bytes(st)   # all donated
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes \
        - ma.alias_size_in_bytes < V5E_HBM
    assert ("tpu_custom_call" in compiled.as_text()) == (backend == "pallas")


def test_smoke_window_spreads_over_four_v5e(topo):
    """The four-chip smoke's shard_map window holds a quarter of the state
    on each chip."""
    devices = topo.devices[:smoke.FOUR_CHIP_P]
    mesh = Mesh(np.array(devices), (smoke.AXIS,),
                axis_types=(jax.sharding.AxisType.Auto,))
    spread = NamedSharding(mesh, PartitionSpec(smoke.AXIS))
    store = smoke.build_store(smoke.FOUR_CHIP_P, smoke.FOUR_CHIP_SLOTS,
                              mesh=mesh)
    st = smoke.abstract_state(store, spread)
    compiled = store.window.lower(st, *_window_lanes(store, spread)).compile()
    per_device = compiled.memory_analysis().argument_size_in_bytes
    assert per_device == pytest.approx(
        smoke.state_bytes(st) / smoke.FOUR_CHIP_P, rel=0.01)
    assert "all-gather" in compiled.as_text()


@functools.lru_cache(maxsize=1)
def _cell_window(topo):
    """The benchmark cell ``ycsb_a.p8``'s window (8 nodes on one v5e),
    compiled once for the tests below: (store, HLO text)."""
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from bench.store import Store
    with open(os.path.join(ROOT, "bench", "configs",
                           "ycsb_1kib_p8.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, "bench", "traffic", "ycsb_a.json")) as f:
        lanes = json.load(f)["lanes_per_participant"]
    store = Store(cfg, lanes, topo.devices)
    return store, store.compile().as_text()


def _computations(hlo):
    """``{computation: [instruction lines]}`` of an HLO module's text."""
    comps, name = {}, None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY\s+)?%(\S+) \(.*\{$", line)
        if head:
            name = head.group(1)
            comps[name] = []
        elif name is not None and re.match(r"^\s+(?:ROOT )?%\S+ = ", line):
            comps[name].append(line)
    return comps


def test_cell_window_probes_index_in_place(topo):
    """The benchmark cell ``ycsb_a.p8``'s window (8 nodes on one v5e)
    reads the index where the entry layout keeps it: no copy of the whole
    ``st.idx`` parameter to the lane-padded {2,1,0} layout."""
    store, hlo = _cell_window(topo)
    entry = hlo[hlo.index("\nENTRY "):]
    entry = entry[:entry.index("\n}\n")]
    shape = rf"s32\[{store.P},{store.kv.C},5\]"
    param = re.search(rf"%(\S+) = {shape}\{{[^}}]*\}} parameter\(\d+\)"
                      r'.*op_name="st\.idx"', entry)
    assert param, "the window takes the index as a parameter"
    padded = re.findall(rf"%\S+ = {shape}\{{2,1,0\b[^}}]*\}} "
                        rf"copy\(%{re.escape(param.group(1))}\)", entry)
    assert not padded, padded


def test_cell_window_writes_rows_only_under_their_lanes(topo):
    """Each whole-table row write of the cell's service round (INSERT
    phase 1, the UPDATE/DELETE write, INSERT phase 2) sits in a ``while``
    body of its own, run only when a lane of its kind is in the window:
    at most one table scatter lies directly in the service loop's body,
    and the entry computation relays the table no more than four
    times."""
    store, hlo = _cell_window(topo)
    kv = store.kv
    item = kv.rows_region.item_shape[0]
    table = (rf"s32\[(?:{item},{store.P * kv.S}|{store.P},{kv.S},{item})\]"
             r"\{[^}]*\}")
    comps = _computations(hlo)
    entry = next(c for c in comps if hlo.count(f"\nENTRY %{c} ") == 1)

    def calls(line):
        return re.findall(r"\bcalls=%([^\s,]+)", line)

    def scatters(comp):          # the computation or one it fuses
        return any(" scatter(" in ln or any(scatters(c) for c in calls(ln))
                   for ln in comps[comp])

    fused = {c for lines in comps.values() for ln in lines for c in calls(ln)}
    writes = {c: [ln for ln in lines
                  if re.search(rf"= {table} (?:scatter|fusion)\(", ln)
                  and (" scatter(" in ln or any(scatters(f)
                                                for f in calls(ln)))]
              for c, lines in comps.items() if c not in fused}
    service = [re.search(r"\bbody=%([^\s,]+)", ln).group(1)
               for ln in comps[entry] if " while(" in ln
               and re.search(r'op_name="[^"]*\(kv\.service\)/while"', ln)]
    assert len(service) == 1, service
    body = service[0]
    own = {c: w for c, w in writes.items() if w and c != body}
    inner = {re.search(r"\bbody=%([^\s,]+)", ln).group(1)
             for ln in comps[body] if " while(" in ln}
    assert sum(map(len, writes.values())) == 3, writes
    assert len(writes[body]) <= 1, writes[body]
    assert all(len(w) == 1 and c in inner for c, w in own.items()), own
    copies = re.findall(rf"= {table} copy\(", "\n".join(comps[entry]))
    assert len(copies) <= 4, copies

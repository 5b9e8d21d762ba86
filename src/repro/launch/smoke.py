"""The KVStore deployment that ``chip_smoke.py`` drives on the chip.

A YCSB-style store (Cooper et al., SoCC 2010): 1 KiB records — YCSB's
ten 100-byte fields, padded to 256 int32 words — loaded to 80% of the
slots, then core workloads A (50% GET / 50% UPDATE) and B (95 / 5) over
Zipfian keys (θ = 0.99).  Every participant runs the linearizable
channel path: ``make_manager`` → ``Runtime`` → ``KVStore.op_window`` /
``get_batch``, jitted with the store state donated so the table is
updated in place.

Record values are a seeded function of ``(key, version)``
(:func:`record_values`), computed on the device inside the jitted
window, so a host oracle needs only one version number per key.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core import GET, INSERT, UPDATE, KVStore, make_manager

AXIS = "nodes"
#: one chip: eight participants under the vmap binding
ONE_CHIP_P = 8
ONE_CHIP_SLOTS = 131072         # per participant: 1,048,576 slots in all
#: four chips: one participant per chip under the shard_map binding
FOUR_CHIP_P = 4
FOUR_CHIP_SLOTS = 1 << 20       # per participant: ~1.1 GiB of rows a chip
VALUE_WIDTH = 256               # int32 words: a 1 KiB record
WINDOW = 256                    # lanes per participant per window
LOAD_FRACTION = 0.8
#: (name, GET share) of the YCSB core workloads the query phase runs
MIXES = (("ycsb_a", 0.5), ("ycsb_b", 0.95))


def record_values(xp, keys, versions, seed: int, width: int = VALUE_WIDTH):
    """(..., width) int32 words of record ``(key, version)``: a lowbias32
    avalanche of key, version, word position and seed.  ``xp`` is
    ``numpy`` (the host oracle) or ``jax.numpy`` (the device)."""
    u32 = xp.uint32
    k = xp.asarray(keys).astype(u32)[..., None]
    v = xp.asarray(versions).astype(u32)[..., None]
    j = xp.arange(1, width + 1, dtype=u32)
    x = (k * u32(0x9E3779B1)) ^ (v * u32(0x85EBCA77)) \
        ^ (j * u32(0xC2B2AE3D)) ^ u32(seed & 0xFFFFFFFF)
    x = x ^ (x >> u32(16))
    x = x * u32(0x7FEB352D)
    x = x ^ (x >> u32(15))
    x = x * u32(0x846CA68B)
    x = x ^ (x >> u32(16))
    return x.view(xp.int32) if xp is np else jax.lax.bitcast_convert_type(
        x, jnp.int32)


class Store(NamedTuple):
    """One built store: its channel, the jitted entry points and the
    shapes a window takes."""

    kv: KVStore
    init: object        # () -> state, placed on the participants' devices
    window: object      # (state, ops, keys, versions) -> (state, KVResult)
    get: object         # (state, keys) -> (state, values, found)
    P: int
    B: int


def build_store(P: int, slots_per_node: int, *, backend=None, mesh=None,
                seed: int = 0, name: str = "ycsb") -> Store:
    """Build the smoke's store on ``P`` participants: the vmap binding on
    one device when ``mesh`` is None, else one participant per device of
    ``mesh`` (axis ``nodes``).  The index holds twice the store's slots,
    since every participant indexes every key."""
    mgr = make_manager(P, axis=AXIS, mesh=mesh, backend=backend)
    kv = KVStore(None, name, mgr, slots_per_node=slots_per_node,
                 value_width=VALUE_WIDTH,
                 index_capacity=2 * P * slots_per_node)

    def window(st, ops, keys, versions):
        values = record_values(jnp, keys, versions, seed)
        return mgr.runtime.run(kv.op_window, st, ops, keys, values)

    def get(st, keys):
        return mgr.runtime.run(kv.get_batch, st, keys)

    # every state and result leaf leads with the participant axis; on a
    # mesh, naming the placement of outputs keeps even the empty leaves
    # on it (jit would otherwise return those replicated)
    out = None
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec
        out = NamedSharding(mesh, PartitionSpec(AXIS))
    return Store(kv, jax.jit(kv.init_state, out_shardings=out),
                 jax.jit(window, donate_argnums=0, out_shardings=out),
                 jax.jit(get, donate_argnums=0, out_shardings=out),
                 P, WINDOW)


def require(ok, msg: str):
    """Fail the smoke (also under ``python -O``, unlike ``assert``)."""
    if not ok:
        raise AssertionError(msg)


class Oracle:
    """Sequential model of the store: the current version of every key
    (-1 = absent), advanced in the channel's linearization order."""

    def __init__(self, n_keys: int):
        self.version = np.full(n_keys + 1, -1, np.int64)

    def check_window(self, ops, keys, versions, found, values, seed: int):
        """Replay one window: GETs linearize at the window start, then
        mutations apply in (participant, lane) order.  Returns the number
        of GETs checked; raises AssertionError on any mismatch."""
        ops, keys = np.asarray(ops).ravel(), np.asarray(keys).ravel()
        versions = np.asarray(versions).ravel()
        found = np.asarray(found).ravel()
        values = np.asarray(values).reshape(ops.shape[0], -1)
        gets = np.flatnonzero(ops == GET)
        pre = self.version[keys[gets]]
        want_found = pre >= 0
        bad = np.flatnonzero(found[gets] != want_found)
        require(bad.size == 0,
                f"GET found mismatch at lanes {gets[bad][:8].tolist()}")
        hit = gets[want_found]
        want = record_values(np, keys[hit], pre[want_found], seed,
                             values.shape[1])
        bad = np.flatnonzero(np.any(values[hit] != want, axis=1))
        require(bad.size == 0,
                f"GET value mismatch at lanes {hit[bad][:8].tolist()}")
        # the smoke inserts only absent keys and updates only present
        # ones, so every mutation must succeed
        for lane in np.flatnonzero((ops == INSERT) | (ops == UPDATE)):
            k = keys[lane]
            require((self.version[k] >= 0) == (ops[lane] == UPDATE),
                    f"workload error: op {ops[lane]} on key {k}")
            require(bool(found[lane]), f"op {ops[lane]} on key {k} failed")
            self.version[k] = versions[lane]
        return gets.size

    def check_gets(self, keys, found, values, seed: int):
        ops = np.full(np.asarray(keys).size, GET, np.int32)
        return self.check_window(ops, keys, np.zeros_like(ops), found,
                                 values, seed)


def state_bytes(state) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(state))


def abstract_state(store: Store, sharding: Optional[object] = None):
    """ShapeDtypeStructs of the store state, optionally placed."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        jax.eval_shape(store.kv.init_state))

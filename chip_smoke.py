"""Bring-up smoke of the KVStore channel path on a TPU.

Run from the checkout root:

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four chips of one host

One chip: the YCSB store of :mod:`repro.launch.smoke` (eight participants
under the vmap binding, 1,048,576 slots of 1 KiB records) is loaded to
80% through INSERT windows, then runs YCSB-A and YCSB-B windows and
``get_batch`` windows, every GET checked against a sequential oracle.
The same windows then run on a second store built on the Pallas
remote-DMA backend: its results and final state must equal the first
bit for bit, and its compiled window must hold the Pallas kernels.

Four chips: the store with one participant per chip (shard_map binding),
its state created sharded, is loaded and queried the same way and
compared bit for bit with the same four-participant program under the
vmap binding on one device of the host.

The script runs everything in this one process, which holds the chips.
It exits non-zero, and prints no result, unless JAX's first device is a
TPU.  Its last line is the JSON result.  The rates it prints are
observations of one smoke run, not a benchmark.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
QUERY_WINDOWS = 4   # per YCSB mix
GET_WINDOWS = 2
#: share of device memory the one-device comparison run may plan to use
FIT_SHARE = 0.9


def log(msg: str):
    print(msg, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the one-chip phases (default); 4: only the "
                         "four-chip shard_map phase and its comparison")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the record values and the workload")
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache(ROOT)

    import jax
    devs = jax.devices()
    platform, kind, count = devs[0].platform, devs[0].device_kind, len(devs)
    log(f"device: platform={platform} kind={kind} count={count}")
    if platform != "tpu":
        print(f"error: JAX found no TPU (first device platform is "
              f"{platform!r}); this smoke runs only on the chip",
              file=sys.stderr)
        return 2
    if count < args.chips:
        print(f"error: --chips {args.chips} needs {args.chips} devices, "
              f"JAX found {count}", file=sys.stderr)
        return 2
    log(f"compile cache: {cache_dir}")

    if args.chips == 4:
        four_chip(args.seed)
    else:
        one_chip(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": count}}))
    return 0


# ---------------------------------------------------------------------------
# workload
# ---------------------------------------------------------------------------

class Workload:
    """Every window a phase runs, made from the seed: the load (INSERT
    windows over a seeded permutation of the keys, version 0), the YCSB
    query windows (one fresh version per lane) and the get_batch keys."""

    def __init__(self, P: int, B: int, n_slots: int, seed: int):
        import numpy as np
        from benchmarks.common import zipf_keys   # YCSB Zipfian, θ = 0.99
        from repro.core import GET, INSERT, NOP, UPDATE
        from repro.launch import smoke

        lanes = P * B
        self.n_load = int(n_slots * smoke.LOAD_FRACTION)
        rng = np.random.default_rng(seed)
        keys = rng.permutation(np.arange(1, self.n_load + 1, dtype=np.uint32))
        n_win = -(-self.n_load // lanes)
        ops = np.full(n_win * lanes, INSERT, np.int32)
        ops[self.n_load:] = NOP
        keys = np.concatenate(
            [keys, np.ones(n_win * lanes - self.n_load, np.uint32)])
        self.load_ops = ops.reshape(n_win, P, B)
        self.load_keys = keys.reshape(n_win, P, B)
        self.zero_versions = np.zeros((P, B), np.uint32)

        self.query = []
        version = 1
        for mix, get_share in smoke.MIXES:
            for _ in range(QUERY_WINDOWS):
                q_ops = np.where(rng.random(lanes) < get_share, GET, UPDATE)
                self.query.append((
                    mix, q_ops.astype(np.int32).reshape(P, B),
                    zipf_keys(rng, lanes, self.n_load).reshape(P, B),
                    np.arange(version, version + lanes,
                              dtype=np.uint32).reshape(P, B)))
                version += lanes
        self.gets = [zipf_keys(rng, lanes, self.n_load).reshape(P, B)
                     for _ in range(GET_WINDOWS)]


# ---------------------------------------------------------------------------
# one store, end to end
# ---------------------------------------------------------------------------

def compile_store(store, sharding, label: str):
    """AOT-compile the store's window and get_batch for state placed by
    ``sharding``; returns (window, get, window memory analysis)."""
    import jax
    from repro.launch import smoke

    st = smoke.abstract_state(store, sharding)
    lane = lambda dt: jax.ShapeDtypeStruct(  # noqa: E731
        (store.P, store.B), dt, sharding=sharding)
    import jax.numpy as jnp
    t0 = time.perf_counter()
    window = store.window.lower(st, lane(jnp.int32), lane(jnp.uint32),
                                lane(jnp.uint32)).compile()
    t1 = time.perf_counter()
    get = store.get.lower(st, lane(jnp.uint32)).compile()
    t2 = time.perf_counter()
    log(f"[{label}] compile seconds: op_window {t1 - t0:.3f}, "
        f"get_batch {t2 - t1:.3f}")
    return window, get, window.memory_analysis()


def run_store(store, compiled, wl: Workload, sharding, label: str):
    """Load the store and run the query and get_batch windows.  Returns
    (host results per window, host final state, per-device state bytes)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import INSERT
    from repro.launch import smoke

    window, get, _ = compiled
    put = lambda x: jax.device_put(x, sharding)  # noqa: E731
    st = store.init()

    t0 = time.perf_counter()
    inserted_ok = []
    for ops, keys in zip(wl.load_ops, wl.load_keys):
        ops_d = put(ops)
        st, res = window(st, ops_d, put(keys), put(wl.zero_versions))
        inserted_ok.append(jnp.all(res.found | (ops_d != INSERT)))
    jax.block_until_ready(st)
    dt = time.perf_counter() - t0
    smoke.require(bool(np.all(jax.device_get(inserted_ok))),
                  "an INSERT of the load reported found=False")
    smoke.require(not bool(np.any(jax.device_get(st.idx_overflow))),
                  "the index overflowed during the load")
    log(f"[{label}] load: {wl.n_load} records in {len(wl.load_ops)} "
        f"windows, {dt:.3f} s, {wl.n_load / dt:.1f} ops/s "
        "(observed, not a benchmark)")

    t0 = time.perf_counter()
    outs = []
    for _mix, ops, keys, versions in wl.query:
        st, res = window(st, put(ops), put(keys), put(versions))
        outs.append(res)
    jax.block_until_ready(outs)
    dt = time.perf_counter() - t0
    n_ops = sum(q[1].size for q in wl.query)
    log(f"[{label}] query: {n_ops} ops in {len(wl.query)} windows "
        f"({', '.join(m for m, _ in smoke.MIXES)}), {dt:.3f} s, "
        f"{n_ops / dt:.1f} ops/s (observed, not a benchmark)")

    gets = []
    for keys in wl.gets:
        st, vals, found = get(st, put(keys))
        gets.append((vals, found))
    jax.block_until_ready(gets)

    per_device = {}
    for leaf in jax.tree.leaves(st):
        for shard in leaf.addressable_shards:
            per_device[shard.device] = per_device.get(shard.device, 0) \
                + shard.data.size * shard.data.dtype.itemsize
    results = jax.device_get((outs, gets))
    state = jax.device_get(st)
    del st
    return results, state, per_device


def check_oracle(wl: Workload, results, seed: int, label: str):
    from repro.launch import smoke

    oracle = smoke.Oracle(wl.n_load)
    oracle.version[1:] = 0        # every loaded key, at version 0
    outs, gets = results
    checked = 0
    for (_mix, ops, keys, versions), res in zip(wl.query, outs):
        checked += oracle.check_window(ops, keys, versions, res.found,
                                       res.value, seed)
    for keys, (vals, found) in zip(wl.gets, gets):
        checked += oracle.check_gets(keys, found, vals, seed)
    log(f"[{label}] oracle: {checked} GETs checked, all match")


def bitwise_equal(a, b) -> bool:
    import jax
    import numpy as np

    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        x.dtype == y.dtype and np.array_equal(x, y) for x, y in zip(la, lb))


def peak_bytes(dev) -> str:
    stats = dev.memory_stats() or {}
    return str(stats.get("peak_bytes_in_use", "not reported"))


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def one_chip(seed: int):
    import jax
    from jax.sharding import SingleDeviceSharding
    from repro.launch import smoke

    dev = jax.devices()[0]
    one = SingleDeviceSharding(dev)
    P, S = smoke.ONE_CHIP_P, smoke.ONE_CHIP_SLOTS
    wl = Workload(P, smoke.WINDOW, P * S, seed)

    ref = smoke.build_store(P, S, seed=seed, name="ycsb")
    log(f"[onesided] {P} participants x {S} slots of "
        f"{smoke.VALUE_WIDTH * 4} B records, state "
        f"{smoke.state_bytes(smoke.abstract_state(ref))} bytes")
    compiled = compile_store(ref, one, "onesided")
    ref_results, ref_state, _ = run_store(ref, compiled, wl, one,
                                          "onesided")
    del compiled
    check_oracle(wl, ref_results, seed, "onesided")
    log(f"[onesided] peak_bytes_in_use {peak_bytes(dev)}")

    dma = smoke.build_store(P, S, backend="pallas", seed=seed,
                            name="ycsb_pallas")
    compiled = compile_store(dma, one, "pallas")
    smoke.require("tpu_custom_call" in compiled[0].as_text(),
                  "the pallas op_window holds no compiled Pallas kernel")
    log("[pallas] compiled op_window holds tpu_custom_call kernels")
    results, state, _ = run_store(dma, compiled, wl, one, "pallas")
    smoke.require(bitwise_equal(results, ref_results),
                  "pallas window results differ from onesided")
    smoke.require(bitwise_equal(state, ref_state),
                  "pallas final state differs from onesided")
    log("[pallas] results and final state bitwise-equal to onesided")
    log(f"[pallas] peak_bytes_in_use {peak_bytes(dev)}")


def four_chip(seed: int):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding
    from repro.launch import smoke

    P = smoke.FOUR_CHIP_P
    dev0 = jax.devices()[0]
    one = SingleDeviceSharding(dev0)
    mesh = jax.make_mesh((P,), (smoke.AXIS,),
                         axis_types=(jax.sharding.AxisType.Auto,),
                         devices=jax.devices()[:P])
    spread = NamedSharding(mesh, PartitionSpec(smoke.AXIS))

    # the one-device comparison run holds all four participants' state:
    # halve the store until its window fits that device
    limit = (dev0.memory_stats() or {}).get("bytes_limit")
    S = smoke.FOUR_CHIP_SLOTS
    while True:
        vm = smoke.build_store(P, S, seed=seed, name=f"ycsb_vmap_{S}")
        try:
            vm_compiled = compile_store(vm, one, "vmap")
        except jax.errors.JaxRuntimeError as e:
            if "RESOURCE_EXHAUSTED" not in str(e) or S <= 8:
                raise
            log(f"[vmap] {P} participants x {S} slots: the compiler "
                "refused it for lack of device memory")
        else:
            ma = vm_compiled[2]
            need = ma.argument_size_in_bytes + ma.temp_size_in_bytes \
                + ma.output_size_in_bytes - ma.alias_size_in_bytes
            log(f"[vmap] {P} participants x {S} slots: arguments "
                f"{ma.argument_size_in_bytes}, temporaries "
                f"{ma.temp_size_in_bytes}, aliased "
                f"{ma.alias_size_in_bytes} bytes; device limit {limit}")
            if limit is None or need <= FIT_SHARE * limit or S <= 8:
                break
        S //= 2
        log(f"slots_per_node halved to {S} for both runs: the one-device "
            "run did not fit")
    wl = Workload(P, smoke.WINDOW, P * S, seed)

    sm = smoke.build_store(P, S, mesh=mesh, seed=seed, name="ycsb_mesh")
    sm_compiled = compile_store(sm, spread, "shard_map")
    results, state, per_device = run_store(sm, sm_compiled, wl, spread,
                                           "shard_map")
    del sm_compiled
    total = sum(per_device.values())
    for d in mesh.devices.flat:
        log(f"[shard_map] {d}: {per_device.get(d, 0)} state bytes of "
            f"{total}, peak_bytes_in_use {peak_bytes(d)}")
    smoke.require(all(per_device.get(d, 0) * P == total
                      for d in mesh.devices.flat),
                  "the state is not spread evenly over the four chips")
    check_oracle(wl, results, seed, "shard_map")

    ref_results, ref_state, _ = run_store(vm, vm_compiled, wl, one, "vmap")
    smoke.require(bitwise_equal(results, ref_results),
                  "shard_map window results differ from the vmap run")
    smoke.require(bitwise_equal(state, ref_state),
                  "shard_map final state differs from the vmap run")
    log("[shard_map] results and final state bitwise-equal to the "
        "one-device vmap run")


if __name__ == "__main__":
    sys.exit(main())

"""The production binding: identical channel code under jax.shard_map over a
real device mesh.  Run in a subprocess so the 8 fake host devices don't leak
into other tests' device state."""
import os
import subprocess
import sys
import textwrap

import pytest

PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import (Barrier, KVStore, SharedQueue, make_manager,
                            INSERT, GET, NOP)

    P = 8
    mesh = jax.make_mesh((P,), ("nodes",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    mgr = make_manager(P, axis="nodes", mesh=mesh)

    # --- barrier under shard_map
    bar = Barrier(None, "bar", mgr)
    st = bar.init_state()
    def prog(s):
        s = bar.wait(s)
        return bar.wait(s)
    st = jax.jit(lambda s: mgr.runtime.run(prog, s))(st)
    assert np.all(np.asarray(st.count) == 2), st.count

    # --- kvstore round-trip under shard_map
    kv = KVStore(None, "kv", mgr, slots_per_node=2, value_width=2,
                 num_locks=4, index_capacity=64)
    kst = kv.init_state()
    step = jax.jit(lambda s, o, k, v: mgr.runtime.run(kv.op_round, s, o, k, v))
    ops = jnp.asarray([INSERT] * P, jnp.int32)
    keys = jnp.arange(1, P + 1, dtype=jnp.uint32)
    vals = jnp.stack([jnp.arange(1, P + 1), jnp.arange(1, P + 1) * 7],
                     axis=1).astype(jnp.int32)
    kst, res = step(kst, ops, keys, vals)
    assert np.all(np.asarray(res.found)), res.found
    gets = jnp.asarray([GET] * P, jnp.int32)
    gkeys = jnp.asarray(list(reversed(range(1, P + 1))), jnp.uint32)
    kst, res = step(kst, gets, gkeys, jnp.zeros((P, 2), jnp.int32))
    assert np.all(np.asarray(res.found))
    want = np.stack([np.asarray(gkeys), np.asarray(gkeys) * 7], axis=1)
    np.testing.assert_array_equal(np.asarray(res.value), want)

    # --- queue under shard_map
    q = SharedQueue(None, "q", mgr, slots_per_node=2, width=1)
    qst = q.init_state()
    def qprog(s, v):
        s, _ = q.enqueue(s, v)
        return q.dequeue(s)
    qst, vals_out, ok = jax.jit(
        lambda s, v: mgr.runtime.run(qprog, s, v))(
        qst, jnp.arange(P, dtype=jnp.int32)[:, None])
    assert np.all(np.asarray(ok))
    np.testing.assert_array_equal(np.asarray(vals_out)[:, 0], np.arange(P))
    print("SHARD_MAP_BINDING_OK")
""")


PROG2 = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import GET, INSERT, KVStore, make_manager
    from repro.core.kvstore import IDX_NODE, IDX_STATE, _USED

    P, B, W = 8, 2, 2
    mesh = jax.make_mesh((P,), ("nodes",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    mgr = make_manager(P, axis="nodes", mesh=mesh)

    kv = KVStore(None, "kv", mgr, slots_per_node=4, value_width=W,
                 num_locks=8, index_capacity=128, placement="explicit")
    st = kv.init_state()
    step = jax.jit(lambda s, o, k, v, t: mgr.runtime.run(
        lambda s_, o_, k_, v_, t_: kv.op_window(s_, o_, k_, v_, targets=t_),
        s, o, k, v, t))
    move = jax.jit(lambda s, k, d, p: mgr.runtime.run(
        lambda s_, k_, d_, p_: kv.migrate_window(s_, k_, d_, preds=p_),
        s, k, d, p))

    def homes(state):
        idx = np.asarray(state.idx[0])
        used = idx[:, IDX_STATE] == _USED
        return {int(np.uint32(r[1])): int(r[IDX_NODE]) for r in idx[used]}

    # --- explicit placement: participant p INSERTs keys (2p+1, 2p+2),
    # homed at key % P — a REMOTE home for most writers.
    keys = np.arange(1, 2 * P + 1, dtype=np.uint32).reshape(P, B)
    vals = jnp.stack([jnp.asarray(keys, jnp.int32) * 10,
                      jnp.asarray(keys, jnp.int32) * 100], axis=-1)
    st, res = step(st, jnp.full((P, B), INSERT, jnp.int32),
                   jnp.asarray(keys), vals, jnp.asarray(keys % P, jnp.int32))
    assert np.all(np.asarray(res.found)), res.found
    assert homes(st) == {int(k): int(k) % P for k in keys.ravel()}, homes(st)

    # --- MOVE under shard_map: re-home every key to (key + 3) % P; one
    # absent-key lane and one pred-masked lane must fail cleanly.
    mkeys = keys.copy(); mkeys[0, 1] = 999         # absent key
    preds = np.ones((P, B), bool); preds[1, 0] = False
    st, moved = move(st, jnp.asarray(mkeys),
                     jnp.asarray((keys + 3) % P, jnp.int32),
                     jnp.asarray(preds))
    moved = np.asarray(moved)
    assert not moved[0, 1] and not moved[1, 0], moved
    assert moved.sum() == P * B - 2, moved
    want = {int(k): (int(k) + 3) % P for k in keys.ravel()}
    want[int(keys[0, 1])] = int(keys[0, 1]) % P    # lane carried 999 instead
    want[int(keys[1, 0])] = int(keys[1, 0]) % P    # pred-masked
    assert homes(st) == want, (homes(st), want)

    # --- values survive the re-home: shifted readers GET every key
    gkeys = np.roll(keys.ravel(), 3).reshape(P, B)
    st, res = step(st, jnp.full((P, B), GET, jnp.int32), jnp.asarray(gkeys),
                   jnp.zeros((P, B, W), jnp.int32),
                   jnp.zeros((P, B), jnp.int32))
    assert np.all(np.asarray(res.found))
    np.testing.assert_array_equal(
        np.asarray(res.value),
        np.stack([gkeys * 10, gkeys * 100], axis=-1).astype(np.int32))
    print("SHARD_MAP_MOVE_OK")
""")


def test_channels_under_shardmap_mesh():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    r = subprocess.run([sys.executable, "-c", PROG], capture_output=True,
                       text=True, env=env, timeout=600)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "SHARD_MAP_BINDING_OK" in r.stdout


def test_move_and_explicit_placement_under_shardmap_mesh():
    """§10 on the production binding: explicit-placement INSERT windows
    and MOVE migration re-home rows correctly on a real 8-device mesh
    axis, not just under the vmap emulation."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    r = subprocess.run([sys.executable, "-c", PROG2], capture_output=True,
                       text=True, env=env, timeout=600)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "SHARD_MAP_MOVE_OK" in r.stdout

"""The plain reference: its window-at-a-time path answers as its
lane-by-lane path does, and the record values are the same on the host
and on the device."""
import jax.numpy as jnp
import numpy as np
import pytest

from bench.reference import (GET, INSERT, UPDATE, SequentialStore,
                             record_values)


def _lane_by_lane(store, ops, keys, versions):
    found = np.zeros(ops.size, bool)
    muts = np.flatnonzero(ops != GET)
    store._apply_in_order(muts, ops, keys, versions, found)
    return found[muts]


@pytest.mark.parametrize("op", [UPDATE, INSERT])
def test_window_at_a_time_equals_lane_by_lane(op):
    rng = np.random.default_rng(5)
    a, b = SequentialStore(50, 3, 4), SequentialStore(50, 3, 4)
    a.version[1:30] = b.version[1:30] = 0
    for w in range(20):
        if op == INSERT:
            keys = rng.permutation(np.arange(1, 51))[:16].astype(np.uint32)
        else:
            keys = rng.integers(1, 51, 16).astype(np.uint32)  # repeats
        ops = np.full(16, op, np.int32)
        versions = np.arange(16 * w + 1, 16 * w + 17, dtype=np.uint32)
        found, _ = a.answer(ops, keys, versions)
        want = _lane_by_lane(b, ops, keys, versions)
        assert np.array_equal(found, want)
        assert np.array_equal(a.version, b.version)


def test_mixed_window_in_lane_order():
    s = SequentialStore(4, 0, 2)
    ops = np.array([INSERT, INSERT, UPDATE, GET], np.int32)
    keys = np.array([1, 1, 1, 1], np.uint32)
    found, values = s.answer(ops, keys, np.array([5, 6, 7, 0], np.uint32))
    # the GET linearizes at the window start, before the INSERT
    assert found.tolist() == [True, False, True, False]
    assert s.version[1] == 7 and not values.any()


def test_record_values_host_equals_device():
    keys = np.arange(1, 9, dtype=np.uint32)
    versions = np.arange(100, 108, dtype=np.uint32)
    seed = 2**31 + 77
    host = record_values(np, keys, versions, seed, 256)
    dev = record_values(jnp, keys, versions,
                        jnp.uint32(seed & 0xFFFFFFFF), 256)
    assert host.dtype == np.int32 and host.shape == (8, 256)
    assert np.array_equal(host, np.asarray(dev))
    assert not np.array_equal(host, record_values(np, keys, versions,
                                                  seed + 1, 256))

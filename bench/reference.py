"""The plain reference the benchmark holds the store to, and the record
values both sides draw from the seed.

Nothing here imports the program.  :class:`SequentialStore` is a
dictionary of the current version of every key, advanced one operation
at a time in the store's linearization order: within a window every GET
linearizes at the window start, then the mutations apply in
(participant, lane) order.  A record's 256 words are a seeded function
of ``(key, version)`` (:func:`record_values`), so the reference keeps one
version number per key.
"""
from __future__ import annotations

import numpy as np

#: the channel's op codes (``repro.core.kvstore``); the entry checks that
#: the program still uses them
NOP, GET, INSERT, UPDATE, DELETE = 0, 1, 2, 3, 4
OP_CODES = {"NOP": NOP, "GET": GET, "INSERT": INSERT, "UPDATE": UPDATE,
            "DELETE": DELETE}
MUTATIONS = (INSERT, UPDATE, DELETE)


def record_values(xp, keys, versions, seed, width: int):
    """(..., width) int32 words of record ``(key, version)``: a lowbias32
    avalanche of key, version, word position and the seed's low 32 bits.
    ``xp`` is ``numpy`` (the reference) or ``jax.numpy`` (the device,
    inside the timed program, where ``seed`` is a uint32 array)."""
    u32 = xp.uint32
    k = xp.asarray(keys).astype(u32)[..., None]
    v = xp.asarray(versions).astype(u32)[..., None]
    j = xp.arange(1, width + 1, dtype=u32)
    s = xp.asarray(seed).astype(u32) if xp is not np else \
        u32(int(seed) & 0xFFFFFFFF)
    x = (k * u32(0x9E3779B1)) ^ (v * u32(0x85EBCA77)) \
        ^ (j * u32(0xC2B2AE3D)) ^ s
    x = x ^ (x >> u32(16))
    x = x * u32(0x7FEB352D)
    x = x ^ (x >> u32(15))
    x = x * u32(0x846CA68B)
    x = x ^ (x >> u32(16))
    if xp is np:
        return x.view(np.int32)
    import jax
    return jax.lax.bitcast_convert_type(x, xp.int32)


class SequentialStore:
    """Sequential model of the store: the current version of every key
    (-1 = absent).  ``check`` replays one window and counts each kind of
    lane whose answer differs; ``answer`` gives the answers themselves."""

    COUNTS = ("get_found_mismatch", "get_value_mismatch",
              "mutation_found_mismatch")

    def __init__(self, n_keys: int, seed: int, width: int):
        self.version = np.full(n_keys + 1, -1, np.int64)
        self.seed = seed
        self.width = width

    def answer(self, ops, keys, versions):
        """Apply one window; returns (found (N,), GET values (N, width) with
        zeros where not found).  Mutations succeed with dict semantics:
        INSERT iff absent, UPDATE and DELETE iff present."""
        ops, keys = np.ravel(ops), np.ravel(keys)
        versions = np.ravel(versions)
        found = np.zeros(ops.shape, bool)
        values = np.zeros(ops.shape + (self.width,), np.int32)
        gets = np.flatnonzero(ops == GET)
        pre = self.version[keys[gets]]
        found[gets] = pre >= 0
        hit = gets[pre >= 0]
        values[hit] = record_values(np, keys[hit], pre[pre >= 0], self.seed,
                                    self.width)
        muts = np.flatnonzero(np.isin(ops, MUTATIONS))
        mk, mo = keys[muts], ops[muts]
        if muts.size and (np.all(mo == UPDATE) or (
                np.all(mo == INSERT) and np.unique(mk).size == mk.size)):
            self._apply_independent(muts, keys, versions, found, mo[0])
        else:
            self._apply_in_order(muts, ops, keys, versions, found)
        return found, values

    def _apply_independent(self, muts, keys, versions, found, op):
        """Mutations of which none changes whether another's key is
        present (all UPDATEs, or INSERTs of distinct keys): the answers of
        :meth:`_apply_in_order`, a window at a time."""
        present = self.version[keys[muts]] >= 0
        ok = present if op == UPDATE else ~present
        found[muts] = ok
        # the last lane of each key in (participant, lane) order wins
        last_k, last_v = keys[muts][ok][::-1], versions[muts][ok][::-1]
        k, first = np.unique(last_k, return_index=True)
        self.version[k] = last_v[first]

    def _apply_in_order(self, muts, ops, keys, versions, found):
        for lane in muts:
            k, op = keys[lane], ops[lane]
            present = self.version[k] >= 0
            ok = (not present) if op == INSERT else present
            found[lane] = ok
            if ok:
                self.version[k] = -1 if op == DELETE else versions[lane]

    def check(self, ops, keys, versions, found, values):
        """Replay one window and count the lanes whose ``found`` or GET
        value differs from the program's."""
        want_found, want_values = self.answer(ops, keys, versions)
        ops = np.ravel(ops)
        found = np.ravel(found)
        values = np.asarray(values).reshape(want_values.shape)
        gets = ops == GET
        muts = np.isin(ops, MUTATIONS)
        both = gets & want_found & found
        return {
            "get_found_mismatch": int(np.sum(gets & (found != want_found))),
            "get_value_mismatch": int(np.sum(
                both & np.any(values != want_values, axis=1))),
            "mutation_found_mismatch": int(np.sum(
                muts & (found != want_found))),
        }

"""Driver of ``KVStore.op_window``: every participant submits one (B,)
window of mixed NOP/GET/INSERT/UPDATE/DELETE lanes per dispatch, served
on the locked path, the store's default.

The timed program is the same compiled window that runs the load.  A
window's answers are each lane's ``found`` flag and each GET's value; the
client waits for them on the host before it sends the next window.
"""
from __future__ import annotations

import jax
import numpy as np

from ..reference import OP_CODES


class Entry:
    def __init__(self, store, seed_d):
        from repro.core import kvstore
        for name, code in OP_CODES.items():
            if getattr(kvstore, name) != code:
                raise RuntimeError(f"op code of {name} is no longer {code}")
        self.store = store
        self.seed_d = seed_d
        self.program = store.window

    def dispatch(self, state, window):
        ops, keys, versions = window
        put = self.store.put
        state, res = self.program(state, put(ops), put(keys), put(versions),
                                  self.seed_d)
        return state, (res.found, res.value)

    @staticmethod
    def fetch(out):
        """Wait for a window's answers and bring them to the host."""
        found, value = jax.device_get(out)
        return np.asarray(found), np.asarray(value)

    @staticmethod
    def check(reference, window, answers):
        ops, keys, versions = window
        found, value = answers
        return reference.check(ops, keys, versions, found, value)

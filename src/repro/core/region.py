"""shared_region — the basic building block of most LOCO channels (§5.1.1).

A symmetric region of memory on each participant; every participant can read
and write all other participants' regions at row granularity.  As in the
paper, the region itself guarantees nothing about consistency — higher
channels layer locks / usage constraints / checksums on top.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from .ack import ALL_PEERS, AckKey, make_ack
from .backends import get_backend
from .colls import verb
from .channel import Channel
from .runtime import Manager


class SharedRegionState(NamedTuple):
    buf: jax.Array  # (slots, *item) per participant; stacked: (P, slots, *item)


class SharedRegion(Channel):
    """Symmetric per-participant buffer of ``slots`` rows of ``item_shape``."""

    def __init__(self, parent, name: str, mgr: Manager, *, slots: int,
                 item_shape: Tuple[int, ...] = (), dtype=jnp.float32,
                 backend=None):
        super().__init__(parent, name, mgr)
        self.slots = int(slots)
        self.item_shape = tuple(item_shape)
        self.dtype = dtype
        # execution protocol for the one-sided verbs (DESIGN.md §14);
        # defaults to the manager's backend
        self.backend = get_backend(backend, default=mgr.backend)
        self.declare_region("buf", (self.slots, *self.item_shape), dtype)

    # -- state ---------------------------------------------------------------
    def init_state(self) -> SharedRegionState:
        """Stacked initial state (leading P axis) for Runtime.run."""
        return SharedRegionState(
            buf=jnp.zeros((self.P, self.slots, *self.item_shape), self.dtype))

    @property
    def item_nbytes(self) -> int:
        import numpy as np
        return int(np.prod(self.item_shape, dtype=np.int64) or 1) * \
            jnp.dtype(self.dtype).itemsize

    # -- local access ----------------------------------------------------------
    def local_read(self, state: SharedRegionState, index):
        return state.buf[index]

    def local_write(self, state: SharedRegionState, index, value,
                    pred=True) -> SharedRegionState:
        cur = state.buf[index]
        return state._replace(buf=state.buf.at[index].set(
            jnp.where(pred, value, cur)))

    def local_write_batch(self, state: SharedRegionState, indices, values,
                          preds=None) -> SharedRegionState:
        """Masked batch of local row writes (no collective, one scatter).

        indices: (R,) int32; values: (R, *item); preds: (R,) bool.  Enabled
        rows must be distinct (the caller's invariant — e.g. the kvstore's
        freshly allocated slots); disabled lanes are dropped, not written.
        """
        if preds is None:
            preds = jnp.ones(values.shape[:1], jnp.bool_)
        row = jnp.where(preds, jnp.clip(indices, 0, self.slots - 1),
                        self.slots)
        return state._replace(buf=state.buf.at[row].set(values, mode="drop"))

    # -- one-sided access (collectively served; see colls.py) -------------------
    @verb
    def read(self, state: SharedRegionState, target, index, pred=True):
        """One-sided read of row ``index`` at participant ``target``."""
        val = self.backend.read(state.buf, target, index, self.axis,
                                pred=pred, ledger=self.mgr.traffic,
                                verb=f"{self.full_name}.read")
        ack = make_ack(val, "read", self.full_name, ALL_PEERS, self.item_nbytes)
        return val, self.mgr.track(ack)

    @verb
    def read_batch(self, state: SharedRegionState, targets, indices,
                   preds=None, coalesce=True):
        """Batched one-sided read; ``coalesce`` (default on) dedupes each
        participant's duplicate (target, index) lanes before the wire
        (DESIGN.md §8.1) — results are bitwise-identical either way."""
        vals = self.backend.read_batch(state.buf, targets, indices, self.axis,
                                       preds=preds, ledger=self.mgr.traffic,
                                       verb=f"{self.full_name}.read_batch",
                                       coalesce=coalesce)
        ack = make_ack(vals, "read", self.full_name, ALL_PEERS,
                       self.item_nbytes * int(targets.shape[0]))
        return vals, self.mgr.track(ack)

    @verb
    def write(self, state: SharedRegionState, target, index, value,
              pred=True):
        """One-sided write of ``value`` to row ``index`` at ``target``."""
        buf = self.backend.write(state.buf, target, index, value, self.axis,
                                 pred=pred, ledger=self.mgr.traffic,
                                 verb=f"{self.full_name}.write")
        new = state._replace(buf=buf)
        ack = make_ack(buf, "write", self.full_name, ALL_PEERS, self.item_nbytes)
        return new, self.mgr.track(ack)

    @verb
    def write_batch(self, state: SharedRegionState, targets, indices, values,
                    preds=None, assume_unique=False):
        buf = self.backend.write_batch(state.buf, targets, indices, values,
                                       self.axis, preds=preds,
                                       assume_unique=assume_unique,
                                       ledger=self.mgr.traffic,
                                       verb=f"{self.full_name}.write_batch")
        new = state._replace(buf=buf)
        ack = make_ack(buf, "write", self.full_name, ALL_PEERS,
                       self.item_nbytes * int(targets.shape[0]))
        return new, self.mgr.track(ack)

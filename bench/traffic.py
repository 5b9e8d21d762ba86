"""The one traffic generator: it reads a mix's parameters from
``traffic/<mix>.json`` and makes every window of a run from the seed.

A mix file holds:

* ``entry``: the channel entry the windows go to (``entries/<entry>.py``);
* ``ops``: the share of each op by name (``GET``, ``UPDATE``, ...);
* ``keys``: ``{"distribution": "zipfian", "theta": 0.99}`` or
  ``{"distribution": "uniform"}``, over the loaded keys;
* ``lanes_per_participant``: the lanes of one participant per window;
* ``loop``: ``closed`` (the next window goes once the previous one's
  answers are back).

The load inserts keys ``1..n`` in an order drawn from the seed, so each
key's home participant (the one that inserted it) is known here.
"""
from __future__ import annotations

import numpy as np

from .reference import INSERT, NOP, OP_CODES


class Traffic:
    def __init__(self, mix: dict, participants: int, n_loaded: int,
                 seed: int):
        if mix["loop"] != "closed":
            raise ValueError(f"loop {mix['loop']!r}: only a closed loop is "
                             "generated")
        self.P = participants
        self.B = int(mix["lanes_per_participant"])
        self.n_loaded = n_loaded
        names = sorted(mix["ops"])
        self.codes = np.array([OP_CODES[n] for n in names], np.int32)
        shares = np.array([mix["ops"][n] for n in names], np.float64)
        if abs(shares.sum() - 1.0) > 1e-9:
            raise ValueError(f"op shares sum to {shares.sum()}, not 1")
        self.op_cdf = np.cumsum(shares)
        dist = mix["keys"]["distribution"]
        if dist == "zipfian":
            # YCSB's Zipfian over the loaded keys, rank r -> key r; the CDF
            # is built once, not per window
            w = np.arange(1, n_loaded + 1, dtype=np.float64) \
                ** -float(mix["keys"]["theta"])
            self.key_cdf = np.cumsum(w) / w.sum()
        elif dist == "uniform":
            self.key_cdf = None
        else:
            raise ValueError(f"key distribution {dist!r} is not generated")
        self.load_rng = np.random.default_rng([seed, 0])
        self.rng = np.random.default_rng([seed, 1])
        self.next_version = 1
        self.home = np.full(n_loaded + 1, -1, np.int32)

    @property
    def lanes(self) -> int:
        return self.P * self.B

    def load_windows(self):
        """(ops, keys, versions) of every INSERT window of the load, each
        (P, B); the tail of the last window is NOP lanes.  Records each
        key's home participant."""
        n, lanes = self.n_loaded, self.lanes
        n_win = -(-n // lanes)
        keys = np.ones(n_win * lanes, np.uint32)
        keys[:n] = self.load_rng.permutation(
            np.arange(1, n + 1, dtype=np.uint32))
        ops = np.full(n_win * lanes, NOP, np.int32)
        ops[:n] = INSERT
        keys = keys.reshape(n_win, self.P, self.B)
        ops = ops.reshape(n_win, self.P, self.B)
        part = np.broadcast_to(np.arange(self.P)[:, None], (self.P, self.B))
        for w in range(n_win):
            live = ops[w] == INSERT
            self.home[keys[w][live]] = part[live]
        zero = np.zeros((self.P, self.B), np.uint32)
        return [(ops[w], keys[w], zero) for w in range(n_win)]

    def next_window(self):
        """The next query window: (ops, keys, versions), each (P, B); every
        lane writes a version of its own."""
        lanes = self.lanes
        ops = self.codes[np.searchsorted(self.op_cdf,
                                         self.rng.random(lanes), side="right")
                         .clip(0, len(self.codes) - 1)]
        if self.key_cdf is None:
            keys = self.rng.integers(1, self.n_loaded + 1, lanes)
        else:
            keys = np.searchsorted(self.key_cdf, self.rng.random(lanes),
                                   side="right").clip(0, self.n_loaded - 1) + 1
        versions = np.arange(self.next_version, self.next_version + lanes,
                             dtype=np.uint32)
        self.next_version += lanes
        shape = (self.P, self.B)
        return (ops.astype(np.int32).reshape(shape),
                keys.astype(np.uint32).reshape(shape), versions.reshape(shape))

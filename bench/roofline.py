"""The least time a window of KVStore ops could take on the chip, from the
ops' semantics alone (never from the compiled program's costs), so that
``window_roofline_pct`` reads the same work whatever implements it.

Bytes of one op:

* GET: one index entry read (5 int32 words, 20 B), one 1,024 B record
  read and one 1,024 B result write to HBM;
* INSERT, UPDATE, DELETE: one index entry and one 1,024 B record write;
* NOP: nothing;
* and, on several chips, the 1,024 B record crosses the interconnect
  once for each op whose key is homed on another chip than the
  requester's.

The least time of a window is the larger of its HBM bytes over the HBM
bandwidth of all its chips and its wire bytes over their interconnect
bandwidth.  Peaks come from ``peaks.json``, keyed by ``device_kind``.
"""
from __future__ import annotations

import json
import os

import numpy as np

from .reference import DELETE, GET, INSERT, UPDATE

INDEX_ENTRY_BYTES = 20
RECORD_BYTES = 1024
PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def peaks(device_kind: str, path: str = PEAKS_FILE) -> dict:
    """The peaks of one chip of ``device_kind``; an unknown kind is an
    error, never a default."""
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in the peaks "
                       f"table ({', '.join(sorted(table))})")
    return table[device_kind]


def window_bytes(ops, key_chip, lane_chip):
    """(HBM bytes, wire bytes) of one window.  ``ops`` are the lanes' op
    codes, ``key_chip`` the chip that holds each lane's key and
    ``lane_chip`` the chip of the participant that sent the lane."""
    ops = np.ravel(ops)
    gets = np.count_nonzero(ops == GET)
    writes = np.count_nonzero(np.isin(ops, (INSERT, UPDATE, DELETE)))
    hbm = gets * (INDEX_ENTRY_BYTES + 2 * RECORD_BYTES) \
        + writes * (INDEX_ENTRY_BYTES + RECORD_BYTES)
    remote = np.isin(ops, (GET, INSERT, UPDATE, DELETE)) \
        & (np.ravel(key_chip) != np.ravel(lane_chip))
    return int(hbm), int(np.count_nonzero(remote) * RECORD_BYTES)


def least_seconds(hbm_bytes, wire_bytes, peak: dict, chips: int) -> float:
    """The least time of work whose bytes spread over ``chips`` chips."""
    return max(hbm_bytes / (chips * peak["hbm_bytes_per_s"]),
               wire_bytes / (chips * peak["ici_bytes_per_s"]))

"""The system under test, built from the program's public API.

``make_manager`` -> ``KVStore`` -> ``Runtime.run(kv.op_window, ...)``,
jitted with the state donated so the table is updated in place.  The
participants run under the vmap binding on one device, or one per device
under shard_map, as the configuration says.  Record values are made on
the device inside the window from ``(key, version, seed)``; the seed is
an argument, so every seed runs the same compiled program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from .reference import record_values

AXIS = "nodes"


class Store:
    def __init__(self, cfg: dict, lanes_per_participant: int, devices):
        from repro.core import KVStore, make_manager

        self.P = int(cfg["participants"])
        self.B = int(lanes_per_participant)
        self.W = int(cfg["value_width"])
        self.chips = int(cfg["chips"])
        binding = cfg["binding"]
        if binding == "shard_map":
            if self.P != self.chips:
                raise ValueError("shard_map places one participant per chip")
            self.mesh = jax.make_mesh(
                (self.P,), (AXIS,), devices=devices[:self.P],
                axis_types=(jax.sharding.AxisType.Auto,))
            self.sharding = NamedSharding(self.mesh, PartitionSpec(AXIS))
            self.replicated = NamedSharding(self.mesh, PartitionSpec())
            self.devices = list(devices[:self.P])
        elif binding == "vmap":
            if self.chips != 1:
                raise ValueError("the vmap binding runs on one chip")
            self.mesh = None
            self.sharding = SingleDeviceSharding(devices[0])
            self.replicated = self.sharding
            self.devices = [devices[0]]
        else:
            raise ValueError(f"binding {binding!r} is not built")
        self.mgr = make_manager(self.P, axis=AXIS, mesh=self.mesh,
                                backend=cfg["backend"])
        self.kv = KVStore(None, "ycsb", self.mgr,
                          slots_per_node=int(cfg["slots_per_node"]),
                          value_width=self.W,
                          index_capacity=int(cfg["index_capacity"]),
                          placement=cfg["placement"])
        out = self.sharding if self.mesh is not None else None
        self.init = jax.jit(self.kv.init_state, out_shardings=out)
        self._window = jax.jit(self._window_fn, donate_argnums=0,
                               out_shardings=out)
        self.window = None          # the AOT-compiled window, see compile()

    def _window_fn(self, st, ops, keys, versions, seed):
        values = record_values(jnp, keys, versions, seed, self.W)
        return self.mgr.runtime.run(self.kv.op_window, st, ops, keys, values)

    def chip_of(self, participant):
        """The chip that holds a participant."""
        return np.asarray(participant) * self.chips // self.P

    def lanes(self, dtype):
        return jax.ShapeDtypeStruct((self.P, self.B), dtype,
                                    sharding=self.sharding)

    def abstract_state(self):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=self.sharding),
            jax.eval_shape(self.kv.init_state))

    def compile(self):
        """AOT-compile the op_window the load and the timed window share."""
        seed = jax.ShapeDtypeStruct((), jnp.uint32,
                                    sharding=self.replicated)
        self.window = self._window.lower(
            self.abstract_state(), self.lanes(jnp.int32),
            self.lanes(jnp.uint32), self.lanes(jnp.uint32), seed).compile()
        return self.window

    def put(self, x):
        return jax.device_put(x, self.sharding)

    def seed_arg(self, seed: int):
        """The seed's low 32 bits, as the window takes it."""
        return jax.device_put(np.uint32(int(seed) & 0xFFFFFFFF),
                              self.replicated)

    def load(self, state, windows, seed_d):
        """Run the load's INSERT windows through the compiled window.
        Returns (state, host found flags of every load window)."""
        found = []
        for ops, keys, versions in windows:
            state, res = self.window(state, self.put(ops), self.put(keys),
                                     self.put(versions), seed_d)
            found.append(res.found)
        return state, [np.asarray(f) for f in jax.device_get(found)]

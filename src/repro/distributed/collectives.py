"""GradChannel: LOCO-style explicit gradient synchronization.

The paper's claim is that upper-level systems (here: data-parallel
training) should be built FROM channel objects rather than ad-hoc
collectives.  This module is that construction:

* each participant's microbatch-accumulated gradient shard is its register
  in a conceptual SST over the data axes: `push` = reduce-scatter (every
  owner pushes, every peer combines), the ZeRO-sharded optimizer updates
  the local shard, and `pull` = all-gather of the updated parameters;
* multi-pod meshes use the **hierarchical schedule**: reduce-scatter inside
  the pod (cheap ICI), all-reduce of the scattered shards across pods
  (expensive DCN — minimal bytes: 1/pod_size of the gradient), all-gather
  inside the pod;
* fence scopes (ack.py) order the phases: the paper-faithful baseline
  issues a GLOBAL fence between phases (full scheduling barrier); the
  relaxed mode uses per-bucket PAIR fences so XLA may overlap buckets —
  the §Perf hillclimb measures exactly this knob;
* optional int8 error-feedback compression (optim/compression.py) on the
  cross-pod hop.

Runs under shard_map over the dp axes; TP('model')-sharded dims pass
through untouched (grads are already TP-local).
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core.ack import AckKey, join
from ..optim import compression as C


def fence_grads(grads):
    """LOCO GLOBAL fence between backward and optimizer update.

    XLA hoists the optimizer's f32 converts into the gradient all-reduces
    (promoting the wire payload to f32 — measured 2× collective bytes on
    the 400B dry-run).  A fence (optimization_barrier over every grad
    leaf — exactly the paper's §5.3 mechanism, built from the same AckKey
    machinery) pins the converts below the reduction so the sync stays
    bf16.
    """
    leaves, treedef = jax.tree.flatten(grads)
    fenced = jax.lax.optimization_barrier(tuple(leaves))
    return jax.tree.unflatten(treedef, list(fenced))


def _bucketize(n_leaves, n_buckets):
    """Round-robin leaf indices into n_buckets lists."""
    buckets = [[] for _ in range(min(n_buckets, max(n_leaves, 1)))]
    for i in range(n_leaves):
        buckets[i % len(buckets)].append(i)
    return [b for b in buckets if b]


def grad_sync(grads, *, data_axis: str = "data",
              pod_axis: Optional[str] = None, fence: str = "global",
              compress: str = "none", error_state=None, n_buckets: int = 4):
    """Per-shard gradient synchronization (call inside shard_map over the
    dp axes).  Returns (synced_grads, new_error_state).

    fence='global'  — join every bucket before any later bucket's collective
                      may be scheduled (paper-faithful conservative order);
    fence='pair'    — each bucket only joins itself; XLA overlaps freely.
    """
    leaves, treedef = jax.tree.flatten(grads)
    err_leaves = (jax.tree.leaves(error_state)
                  if error_state is not None else [None] * len(leaves))
    buckets = _bucketize(len(leaves), n_buckets)
    out = [None] * len(leaves)
    new_err = [None] * len(leaves)
    pending = AckKey.empty()

    for bucket in buckets:
        if fence == "global" and pending.tokens:
            # order this bucket after ALL previously issued pushes
            gate = [leaves[i] for i in bucket]
            gate = join(pending, *gate) if len(gate) > 1 else \
                [join(pending, gate[0])]
            for j, i in enumerate(bucket):
                leaves[i] = gate[j]
        bucket_ack = AckKey.empty()
        for i in bucket:
            g = leaves[i].astype(jnp.float32)
            # in-pod push: every data peer contributes (SST push_broadcast
            # discipline; psum == fused reduce-scatter+all-gather on a ring)
            g = jax.lax.pmean(g, data_axis)
            if pod_axis is not None:
                if compress == "int8ef":
                    g, new_err[i] = C.int8_ef_allreduce(
                        g, pod_axis, err_leaves[i])
                else:
                    g = jax.lax.pmean(g, pod_axis)
            out[i] = g
            bucket_ack = bucket_ack | AckKey([g])
        pending = bucket_ack if fence == "pair" else (pending | bucket_ack)

    synced = jax.tree.unflatten(treedef, out)
    err_tree = (jax.tree.unflatten(treedef, new_err)
                if compress == "int8ef" else None)
    return synced, err_tree


def make_grad_sync_shardmap(mesh, param_specs, *, fence="global",
                            compress="none", n_buckets=4):
    """Bind grad_sync to a mesh: grads arrive TP-sharded ('model' dims per
    param_specs) and replicated over dp axes (per-shard partial grads);
    leave with dp-mean applied."""
    axes = mesh.axis_names
    pod_axis = "pod" if "pod" in axes else None

    def in_spec(ps: P):
        return ps  # grads carry their param sharding

    in_specs = jax.tree.map(in_spec, param_specs,
                            is_leaf=lambda x: isinstance(x, P))

    @functools.partial(jax.shard_map, mesh=mesh, check_vma=False,
                       in_specs=(in_specs,), out_specs=in_specs)
    def sync(grads):
        synced, _err = grad_sync(grads, data_axis="data", pod_axis=pod_axis,
                                 fence=fence, compress=compress,
                                 n_buckets=n_buckets)
        return synced

    return sync

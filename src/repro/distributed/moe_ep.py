"""Expert-parallel MoE wiring: the shard_map region around moe_block_a2a.

This is the framework's clearest channel-object instantiation (DESIGN.md
§3): the dispatch buffer is a striped shared_region of (expert, capacity)
slots; tokens are one-sided-written to the expert's host shard and the
results one-sided-read back — realized as the two all-to-alls in
models/moe.py.  This module binds that per-shard math to the mesh.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..configs.base import ArchConfig
from ..models import moe as M
from .sharding import TP, dp_axes


def make_moe_fn(cfg: ArchConfig, mesh):
    """Returns moe_fn(ffn_params, x, cfg) -> (out, aux) running the
    expert-parallel a2a block under shard_map over the 'model' axis."""
    dp = dp_axes(mesh)

    def param_specs(params):
        def spec(path_leaf):
            return None
        # experts sharded over model axis (EP); router/shared replicated
        return {
            "router": P(),
            "experts": jax.tree.map(lambda _: P(TP, None, None),
                                    params["experts"]),
            **({"shared": jax.tree.map(lambda _: P(), params["shared"])}
               if "shared" in params else {}),
        }

    def moe_fn(params, x, _cfg):
        B, S, d = x.shape
        x_spec = P(dp if B % _dp_total(mesh) == 0 else None,
                   TP if S % mesh.shape[TP] == 0 else None, None)

        @functools.partial(
            jax.shard_map, mesh=mesh, check_vma=False,
            in_specs=(param_specs(params), x_spec),
            out_specs=(x_spec, P()))
        def run(p, xl):
            out, aux = M.moe_block_a2a(p, xl, cfg, TP)
            # aux is per-shard; average over the whole mesh for a replicated
            # scalar (out_specs P() requires a collective here)
            aux = jax.lax.pmean(aux, tuple(mesh.axis_names))
            return out, aux

        return run(params, x)

    return moe_fn


def _dp_total(mesh) -> int:
    t = 1
    for a in dp_axes(mesh):
        t *= mesh.shape[a]
    return max(t, 1)

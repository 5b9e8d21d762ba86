"""Integration: train loop + optimizer + checkpoint + data pipeline on a
1-device mesh (the production code path, minus scale)."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.configs import get_smoke_config
from repro.configs.base import ShapeConfig, TrainConfig
from repro.data import SyntheticTokens
from repro.distributed import sharding as SH
from repro.optim import make_optimizer, opt_state_pspecs
from repro.train import make_train_step


def tiny_mesh():
    from repro.launch.mesh import make_debug_mesh
    return make_debug_mesh(1, 1)


def setup(arch="llama3.2-3b", **tkw):
    cfg = get_smoke_config(arch).replace(dtype="float32")
    tcfg = TrainConfig(lr=1e-3, **tkw)
    mesh = tiny_mesh()
    model, opt, train_step, jit_factory = make_train_step(
        cfg, tcfg, mesh)
    params = model.init(jax.random.PRNGKey(0))
    opt_state = opt.init(params)
    pipe = SyntheticTokens(cfg, batch=4, seq=16, seed=0)
    return cfg, tcfg, mesh, model, opt, train_step, params, opt_state, pipe


class TestTrainLoop:
    def test_loss_decreases_over_steps(self):
        (cfg, tcfg, mesh, model, opt, train_step, params, opt_state,
         pipe) = setup()
        step_fn = jax.jit(train_step)
        losses = []
        for step in range(8):
            batch = jax.tree.map(jnp.asarray, pipe.get_batch(0))  # same data
            params, opt_state, metrics = step_fn(params, opt_state, batch)
            losses.append(float(metrics["loss"]))
        assert all(np.isfinite(losses))
        assert losses[-1] < losses[0]  # memorizes the repeated batch

    def test_microbatch_accumulation_matches_full_batch(self):
        (cfg, tcfg, mesh, model, opt, train_step, params, opt_state,
         pipe) = setup()
        tcfg2 = TrainConfig(lr=1e-3, microbatch=2)
        _m, _o, train_step2, _ = make_train_step(cfg, tcfg2, mesh)
        batch = jax.tree.map(jnp.asarray, pipe.get_batch(0))
        p1, _, m1 = jax.jit(train_step)(params, opt_state, batch)
        p2, _, m2 = jax.jit(train_step2)(params, opt_state, batch)
        # same total gradient → same updated params (up to accumulation fp)
        d = jax.tree.map(
            lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                               - b.astype(jnp.float32)))),
            p1, p2)
        assert max(jax.tree.leaves(d)) < 5e-5

    def test_adafactor_runs(self):
        (cfg, tcfg, mesh, model, opt, train_step, params, opt_state,
         pipe) = setup(optimizer="adafactor")
        batch = jax.tree.map(jnp.asarray, pipe.get_batch(0))
        params, opt_state, metrics = jax.jit(train_step)(
            params, opt_state, batch)
        assert np.isfinite(float(metrics["loss"]))


class TestShardingRules:
    def test_param_specs_resolve_for_all_archs(self):
        from repro.configs import ARCH_IDS
        from repro.models import build_model
        mesh = tiny_mesh()
        for arch in ARCH_IDS:
            cfg = get_smoke_config(arch)
            model = build_model(cfg)
            shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
            specs = jax.tree.map(
                lambda x: None, SH.param_pspecs(shapes, mesh),
                is_leaf=lambda x: hasattr(x, "_normalized_spec") or
                str(type(x).__name__) == "PartitionSpec")
            assert len(jax.tree.leaves(shapes)) > 0

    def test_tp_rules_shard_attention_and_ffn(self):
        import re

        from repro.distributed.sharding import PARAM_RULES, _resolve_template
        mesh = jax.sharding.AbstractMesh((1, 4), ("data", "model"))
        # wq (d=64, H*hd=64): shardable over 4
        for pat, template in PARAM_RULES:
            if re.search(pat, "stack/super/0/attn/wq"):
                spec = _resolve_template(template, (64, 64), mesh)
                assert spec[1] == "model"
                break
        # vocab embedding row-sharded
        for pat, template in PARAM_RULES:
            if re.search(pat, "embed/table"):
                spec = _resolve_template(template, (256, 64), mesh)
                assert spec[0] == "model"
                break

    def test_zero_specs_shard_moments_over_data(self):
        cfg = get_smoke_config("qwen3-8b").replace(dtype="float32")
        from repro.models import build_model
        mesh = jax.sharding.AbstractMesh((4, 1), ("data", "model"))
        model = build_model(cfg)
        params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        opt = make_optimizer(TrainConfig(zero_stage=2))
        ostate = jax.eval_shape(opt.init, params)
        pspecs = SH.param_pspecs(params, mesh)
        ospecs = opt_state_pspecs(ostate, pspecs, mesh, zero_stage=2)
        # at least one moment leaf picked up the data axis
        found = any(
            any(ax == ("data",) or ax == "data" or
                (isinstance(ax, tuple) and "data" in ax)
                for ax in spec if ax is not None)
            for spec in jax.tree.leaves(
                ospecs.mu, is_leaf=lambda x: type(x).__name__ ==
                "PartitionSpec"))
        assert found


class TestCheckpoint:
    def test_save_restore_roundtrip_and_continuity(self, tmp_path):
        (cfg, tcfg, mesh, model, opt, train_step, params, opt_state,
         pipe) = setup()
        step_fn = jax.jit(train_step)
        mgr = CheckpointManager(str(tmp_path), keep_last=2)
        for step in range(3):
            batch = jax.tree.map(jnp.asarray, pipe.get_batch(step))
            params, opt_state, metrics = step_fn(params, opt_state, batch)
        mgr.save(2, {"params": params, "opt": opt_state}, blocking=True)

        batch3 = jax.tree.map(jnp.asarray, pipe.get_batch(3))
        p4, o4, m4 = step_fn(params, opt_state, batch3)

        restored = mgr.restore(2, {"params": params, "opt": opt_state})
        p4r, o4r, m4r = step_fn(restored["params"], restored["opt"], batch3)
        assert float(m4["loss"]) == pytest.approx(float(m4r["loss"]),
                                                  rel=1e-6)

    def test_atomic_commit_and_gc(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep_last=2)
        tree = {"x": jnp.arange(8.0), "y": {"z": jnp.ones((2, 2))}}
        for s in (1, 2, 3, 4):
            mgr.save(s, tree, blocking=True)
        assert mgr.steps() == [3, 4]
        back = mgr.restore(4, tree)
        np.testing.assert_array_equal(np.asarray(back["x"]), np.arange(8.0))

    def test_pipeline_determinism_and_resume(self):
        cfg = get_smoke_config("qwen3-8b")
        p1 = SyntheticTokens(cfg, batch=4, seq=8, seed=7)
        p2 = SyntheticTokens(cfg, batch=4, seq=8, seed=7)
        np.testing.assert_array_equal(p1.get_batch(5)["tokens"],
                                      p2.get_batch(5)["tokens"])
        assert not np.array_equal(p1.get_batch(5)["tokens"],
                                  p1.get_batch(6)["tokens"])


class TestChunkedXent:
    def test_chunked_xent_matches_dense_loss_and_grads(self):
        import jax
        import jax.numpy as jnp
        from repro.train import make_train_step
        cfg = get_smoke_config("qwen3-8b").replace(dtype="float32")
        mesh = tiny_mesh()
        pipe = SyntheticTokens(cfg, batch=4, seq=16, seed=3)
        batch = jax.tree.map(jnp.asarray, pipe.get_batch(0))
        m1, o1, s1, _ = make_train_step(cfg, TrainConfig(lr=1e-3), mesh)
        m2, o2, s2, _ = make_train_step(
            cfg, TrainConfig(lr=1e-3, xent_chunks=4), mesh)
        params = m1.init(jax.random.PRNGKey(0))
        (l1, _), g1 = jax.value_and_grad(m1.train_loss, has_aux=True)(
            params, batch)
        (l2, _), g2 = jax.value_and_grad(m2.train_loss, has_aux=True)(
            params, batch)
        assert float(l1) == pytest.approx(float(l2), rel=1e-5)
        d = max(jax.tree.leaves(jax.tree.map(
            lambda a, b: float(jnp.max(jnp.abs(a - b))), g1, g2)))
        assert d < 1e-4

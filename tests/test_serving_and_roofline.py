"""Serving-engine integration + roofline-analysis unit tests +
error-feedback compression property."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.roofline import analysis as RA


class TestServingEngine:
    def test_generate_with_channel_page_table(self):
        from repro.serving.engine import MAX_WINDOW, P_NODES, ServingEngine
        cfg = get_smoke_config("llama3.2-3b").replace(dtype="float32")
        eng = ServingEngine(cfg, max_batch=2, max_seq=48)
        # lock stripe must cover the outstanding (P, MAX_WINDOW) window —
        # an undersized stripe degrades windows to max-queue-depth rounds
        assert eng.pages.L >= P_NODES * MAX_WINDOW
        rng = np.random.default_rng(0)
        prompts = [rng.integers(1, cfg.vocab, size=(12,)).astype(np.int32)
                   for _ in range(4)]
        outs = eng.generate(prompts, gen_len=4)
        assert len(outs) == 4 and all(len(o) == 4 for o in outs)
        stats = eng.stats()
        from repro.core import DELETE, GET, INSERT
        # every admitted request inserted then deleted its pages; decode
        # rounds did lock-free gets
        assert stats["kv_ops"][INSERT] == stats["kv_ops"][DELETE]
        assert stats["kv_ops"][GET] >= 4
        assert "modeled_wire_bytes" in stats
        # §10.3 deferral visibility: admission-time explicit placement
        # never runs a rebalance, so the backlog must read zero (the
        # counter itself is exercised in test_locality.py)
        assert stats["locality"]["migration_backlog"] == 0

    def test_generate_with_replicated_page_table(self):
        """replicas= mode (DESIGN.md §9.3): every mutation window is
        published through the ReplicatedLog and the follower page tables
        stay bitwise-converged with the leader through a full serve."""
        from repro.serving.engine import ServingEngine
        cfg = get_smoke_config("llama3.2-3b").replace(dtype="float32")
        eng = ServingEngine(cfg, max_batch=2, max_seq=32, replicas=2)
        rng = np.random.default_rng(1)
        prompts = [rng.integers(1, cfg.vocab, size=(8,)).astype(np.int32)
                   for _ in range(2)]
        outs = eng.generate(prompts, gen_len=2)
        assert len(outs) == 2 and all(len(o) == 2 for o in outs)
        rep = eng.stats()["replication"]
        assert rep["replicas"] == 2
        assert rep["published"] >= 2 and rep["dropped"] == 0
        assert rep["lag"] == 0, "sync-after-append leaves zero lag"
        assert rep["diverged_leaves"] == [0, 0], \
            "follower page tables must stay bitwise-equal to the leader"


class TestRooflineAnalysis:
    def test_collective_parser_shapes_and_ring_model(self):
        hlo = """
ENTRY %main () -> f32[] {
  %ag = bf16[8,128]{1,0} all-gather(bf16[8,8]{1,0} %x), replica_groups=[16,16]<=[256], dimensions={1}
  %ar = f32[4,4]{1,0} all-reduce(f32[4,4]{1,0} %y), replica_groups={{0,1,2,3}}
  %tup = (f32[2,2]{1,0}, f32[8]{0}) all-reduce(%a, %b), replica_groups=[2,128]<=[256]
}
"""
        out = RA.collective_bytes(hlo, 256)
        # ag: result 8*128*2 = 2048 B × 15/16
        assert out["per_op_bytes"]["all-gather"] == pytest.approx(
            2048 * 15 / 16)
        # ar: 2 × 3/4 × 64 B
        ar = out["per_op_bytes"]["all-reduce"]
        assert ar == pytest.approx(2 * (3 / 4) * 64 + 2 * (127 / 128) * 48)
        # f32 reductions tracked for the TPU-native correction
        assert out["f32_reduce_bytes"] > 0
        assert out["total_bytes_tpu_native"] < out["total_bytes"]

    def test_remote_dma_custom_call_accounting(self):
        """§15: the Pallas ``make_async_remote_copy`` wire hop compiles to
        a custom-call carrying the kernel name in its metadata, never a
        named HLO collective — the parser costs the result payload as one
        point-to-point hop, and ignores both unmarked custom-calls and
        marker words outside custom-call lines."""
        hlo = """
ENTRY %main () -> f32[] {
  %send = f32[4,128]{1,0} custom-call(f32[4,128]{1,0} %src), custom_call_target="tpu_custom_call", metadata={op_name="pallas_call[name=remote_copy]"}
  %tup = (f32[2,2]{1,0}, s32[8]{0}) custom-call-start(%a), backend_config="async_remote_copy"
  %plain = f32[64]{0} custom-call(f32[64]{0} %b), custom_call_target="Sharding"
  %fus = f32[64]{0} fusion(f32[64]{0} %c), calls=%remote_dma_helper
}
"""
        out = RA.collective_bytes(hlo, 8)
        assert out["per_op_bytes"]["remote-dma"] == pytest.approx(
            4 * 128 * 4 + (2 * 2 * 4 + 8 * 4))
        assert out["per_op_count"]["remote-dma"] == 2
        assert out["total_bytes"] == pytest.approx(
            out["per_op_bytes"]["remote-dma"])

    def test_extrapolation_is_affine(self):
        c1 = {"flops": 100.0, "bytes": 10.0,
              "coll": {"total_bytes": 7.0, "per_op_bytes": {"all-reduce": 7.0},
                       "per_op_count": {"all-reduce": 2},
                       "f32_reduce_bytes": 0.0}}
        c2 = {"flops": 150.0, "bytes": 14.0,
              "coll": {"total_bytes": 9.0, "per_op_bytes": {"all-reduce": 9.0},
                       "per_op_count": {"all-reduce": 3},
                       "f32_reduce_bytes": 0.0}}
        out = RA.extrapolate_costs(c1, c2, 1, 2, 10)
        assert out["flops"] == pytest.approx(100 + 9 * 50)   # base + n·per
        assert out["coll"]["per_op_bytes"]["all-reduce"] == pytest.approx(
            7 + 9 * 2)

    def test_in_loop_collective_detector(self):
        hlo = """
%body.1 (p: (s32[])) -> (s32[]) {
  %r = f32[4]{0} all-reduce(f32[4]{0} %g), replica_groups={{0,1}}
}
ENTRY %main () -> s32[] {
  %w = (s32[]) while((s32[]) %init), condition=%cond.1, body=%body.1
}
"""
        assert RA._while_body_collectives(hlo) == 1

    def test_analytic_memory_decode_is_weights_plus_cache(self):
        from repro.configs import get_config
        from repro.configs.base import LM_SHAPES
        cfg = get_config("llama3.2-3b")
        decode = [s for s in LM_SHAPES if s.name == "decode_32k"][0]
        got = RA.analytic_hbm_bytes(cfg, decode, 256)
        weights = cfg.param_count(active_only=True) / 16 * 2
        cache = RA._cache_bytes(cfg, decode, 256)
        assert got == pytest.approx(weights + cache, rel=0.2)


class TestCompressionProperty:
    def test_error_feedback_sum_converges(self):
        """EF guarantee: cumulative applied ≈ cumulative true gradient."""
        from repro.optim.compression import int8_ef_allreduce

        rng = np.random.default_rng(0)
        P = 4
        true_sum = np.zeros((16,), np.float32)
        applied_sum = np.zeros((16,), np.float32)
        err = jnp.zeros((P, 16), jnp.float32)

        @jax.jit
        def step(gs, err):
            def f(g, e):
                return int8_ef_allreduce(g, "p", e)
            return jax.vmap(f, axis_name="p")(gs, err)

        for t in range(30):
            gs = rng.standard_normal((P, 16)).astype(np.float32)
            true_sum += gs.mean(axis=0)
            out, err = step(jnp.asarray(gs), err)
            applied_sum += np.asarray(out)[0]
        # cumulative deviation bounded by one quantization step, not O(T)
        scale = np.abs(true_sum).max()
        assert np.abs(applied_sum - true_sum).max() < 0.05 * scale + 0.1

"""Per-kernel validation: interpret-mode Pallas vs pure-jnp oracle, swept
over shapes and dtypes."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

jax.config.update("jax_default_matmul_precision", "highest")


def rand(shape, dtype, rng, scale=1.0):
    x = rng.standard_normal(shape).astype(np.float32) * scale
    return jnp.asarray(x, dtype)


def tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 else \
        dict(atol=2e-5, rtol=2e-5)


def close(a, b, dtype):
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), **tol(dtype))


# --------------------------------------------------------------- flash attn
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,Hq,Hkv,Sq,Sk,D,causal,window",
    [
        (1, 4, 4, 128, 128, 64, True, None),      # MHA causal
        (2, 8, 2, 256, 256, 64, True, None),      # GQA
        (1, 4, 1, 128, 128, 128, True, None),     # MQA
        (1, 2, 2, 128, 384, 64, True, None),      # chunked prefill offset
        (1, 4, 4, 100, 100, 64, True, None),      # ragged → padding path
        (1, 2, 2, 256, 256, 64, True, 64),        # sliding window
        (1, 2, 2, 128, 128, 64, False, None),     # bidirectional (encoder)
        (1, 2, 1, 64, 192, 256, True, None),      # gemma head_dim 256
    ])
def test_flash_attention_matches_oracle(B, Hq, Hkv, Sq, Sk, D, causal,
                                        window, dtype):
    rng = np.random.default_rng(0)
    q = rand((B, Hq, Sq, D), dtype, rng)
    k = rand((B, Hkv, Sk, D), dtype, rng)
    v = rand((B, Hkv, Sk, D), dtype, rng)
    out = ops.flash_attention(q, k, v, causal=causal, window=window,
                              block_q=64, block_k=64)
    want = ref.mha(q, k, v, causal=causal, window=window)
    close(out, want, dtype)


def test_flash_attention_blocksize_invariance():
    rng = np.random.default_rng(1)
    q = rand((1, 2, 256, 64), jnp.float32, rng)
    k = rand((1, 2, 256, 64), jnp.float32, rng)
    v = rand((1, 2, 256, 64), jnp.float32, rng)
    o1 = ops.flash_attention(q, k, v, block_q=64, block_k=64)
    o2 = ops.flash_attention(q, k, v, block_q=128, block_k=256)
    close(o1, o2, jnp.float32)


# --------------------------------------------------------------- decode attn
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,Hq,Hkv,S,D",
    [(2, 4, 4, 512, 64), (2, 8, 2, 512, 64), (1, 16, 1, 1024, 128),
     (3, 8, 4, 300, 64)])
def test_decode_attention_matches_oracle(B, Hq, Hkv, S, D, dtype):
    rng = np.random.default_rng(2)
    q = rand((B, Hq, D), dtype, rng)
    kc = rand((B, Hkv, S, D), dtype, rng)
    vc = rand((B, Hkv, S, D), dtype, rng)
    lengths = jnp.asarray(rng.integers(1, S + 1, size=(B,)), jnp.int32)
    out = ops.decode_attention(q, kc, vc, lengths, block_k=128)
    want = ref.decode_attention(q, kc, vc, lengths)
    close(out, want, dtype)


def test_decode_attention_respects_lengths():
    """Tokens past ``lengths`` must not affect the output at all."""
    rng = np.random.default_rng(3)
    B, H, S, D = 1, 2, 256, 64
    q = rand((B, H, D), jnp.float32, rng)
    kc = rand((B, H, S, D), jnp.float32, rng)
    vc = rand((B, H, S, D), jnp.float32, rng)
    lengths = jnp.asarray([100], jnp.int32)
    out1 = ops.decode_attention(q, kc, vc, lengths, block_k=128)
    kc2 = kc.at[:, :, 100:].set(999.0)
    vc2 = vc.at[:, :, 100:].set(-999.0)
    out2 = ops.decode_attention(q, kc2, vc2, lengths, block_k=128)
    close(out1, out2, jnp.float32)


# -------------------------------------------------------------------- RG-LRU
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,D", [(2, 256, 256), (1, 512, 512),
                                   (2, 200, 256)])
def test_rglru_matches_oracle(B, S, D, dtype):
    rng = np.random.default_rng(4)
    x = rand((B, S, D), dtype, rng)
    log_a = -jnp.abs(rand((B, S, D), dtype, rng, scale=0.5)) - 0.01
    y, h = ops.rglru(x, log_a, block_s=128, block_d=128)
    y_ref, h_ref = ref.rglru(x, log_a)
    close(y, y_ref, dtype)
    close(h, h_ref, dtype)


def test_rglru_carry_across_time_blocks():
    """The recurrence must thread h across time-block boundaries exactly."""
    rng = np.random.default_rng(5)
    x = rand((1, 512, 128), jnp.float32, rng)
    log_a = -jnp.abs(rand((1, 512, 128), jnp.float32, rng, scale=0.3)) - 0.01
    y1, _ = ops.rglru(x, log_a, block_s=64, block_d=128)
    y2, _ = ops.rglru(x, log_a, block_s=512, block_d=128)
    close(y1, y2, jnp.float32)


# --------------------------------------------------------------------- WKV6
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,S,D", [(1, 2, 128, 64), (2, 4, 256, 64),
                                     (1, 2, 100, 128)])
def test_wkv6_matches_oracle(B, H, S, D, dtype):
    rng = np.random.default_rng(6)
    r = rand((B, H, S, D), dtype, rng)
    k = rand((B, H, S, D), dtype, rng, scale=0.5)
    v = rand((B, H, S, D), dtype, rng)
    w = jnp.asarray(
        np.exp(-np.exp(rng.standard_normal((B, H, S, D)) * 0.5)), dtype)
    u = rand((H, D), dtype, rng, scale=0.5)
    y, s_fin = ops.wkv6(r, k, v, w, u, block_s=64)
    y_ref, s_ref = ref.wkv6(r, k, v, w, u)
    close(y, y_ref, dtype)
    close(s_fin, s_ref, dtype)


def test_wkv6_state_carry_across_blocks():
    rng = np.random.default_rng(7)
    B, H, S, D = 1, 1, 256, 64
    r = rand((B, H, S, D), jnp.float32, rng)
    k = rand((B, H, S, D), jnp.float32, rng, scale=0.5)
    v = rand((B, H, S, D), jnp.float32, rng)
    w = jnp.asarray(np.exp(-np.exp(rng.standard_normal((B, H, S, D)) * 0.5)),
                    jnp.float32)
    u = rand((H, D), jnp.float32, rng)
    y1, s1 = ops.wkv6(r, k, v, w, u, block_s=32)
    y2, s2 = ops.wkv6(r, k, v, w, u, block_s=256)
    close(y1, y2, jnp.float32)
    close(s1, s2, jnp.float32)


# ---------------------------------------------------------------------- GMM
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("E,T,Din,Dout,BT", [(4, 512, 256, 256, 128),
                                             (8, 1024, 512, 256, 128),
                                             (2, 256, 128, 512, 64)])
def test_gmm_matches_oracle(E, T, Din, Dout, BT, dtype):
    rng = np.random.default_rng(8)
    x = rand((T, Din), dtype, rng)
    w = rand((E, Din, Dout), dtype, rng, scale=0.2)
    block_expert = jnp.asarray(
        np.sort(rng.integers(0, E, size=(T // BT,))), jnp.int32)
    out = ops.gmm(x, w, block_expert, block_t=BT, block_n=128, block_k=128)
    want = ref.gmm(x, w, block_expert, BT)
    close(out, want, dtype)


# --------------------------------------------------------------- remote DMA
from repro.kernels import remote_dma as rdma  # noqa: E402


class TestRemoteDma:
    """A/B: interpret-mode DMA kernels vs their jnp oracles — values AND
    the measured byte counters, which must come from the same masks that
    drive the copies (the §15 measured tier's ground truth)."""

    def _rng(self, seed=0):
        return np.random.default_rng(seed)

    @pytest.mark.parametrize("R", [1, 4, 9])
    def test_build_descriptors_matches_oracle(self, R):
        rng = self._rng(R)
        tg = jnp.asarray(rng.integers(0, 4, (R,)).astype(np.int32))
        ix = jnp.asarray(rng.integers(0, 8, (R,)).astype(np.int32))
        en = jnp.asarray(rng.integers(0, 2, (R,)).astype(np.int32))
        wire = jnp.asarray(rng.integers(0, 2, (R,)).astype(np.int32))
        d_k, nb_k = rdma.build_descriptors(tg, ix, en, wire=wire,
                                           op=rdma.OP_WRITE, row_nbytes=20)
        d_r, nb_r = rdma.build_descriptors(tg, ix, en, wire=wire,
                                           op=rdma.OP_WRITE, row_nbytes=20,
                                           force_ref=True)
        np.testing.assert_array_equal(np.asarray(d_k), np.asarray(d_r))
        assert int(nb_k) == int(nb_r) == \
            int(np.sum(np.asarray(wire))) * rdma.DESC_BYTES
        # descriptor columns carry exactly what colls reads back
        d = np.asarray(d_k)
        assert (d[:, 0] == rdma.OP_WRITE).all()
        np.testing.assert_array_equal(d[:, 1], np.asarray(tg))
        np.testing.assert_array_equal(d[:, 2], np.asarray(ix))
        np.testing.assert_array_equal(d[:, 3], np.asarray(en))
        assert (d[:, 4] == 20).all()
        np.testing.assert_array_equal(d[:, 5], np.arange(R))

    @pytest.mark.parametrize("dtype", [jnp.int32, jnp.float32])
    def test_gather_rows_matches_oracle(self, dtype):
        rng = self._rng(1)
        buf = jnp.asarray(rng.integers(-99, 99, (8, 5))).astype(dtype)
        ix = jnp.asarray(rng.integers(0, 8, (12,)).astype(np.int32))
        mask = jnp.asarray(rng.integers(0, 2, (12,)).astype(np.int32))
        rows_k, nb_k = rdma.gather_rows(buf, ix, mask)
        rows_r, nb_r = rdma.gather_rows(buf, ix, mask, force_ref=True)
        np.testing.assert_array_equal(np.asarray(rows_k),
                                      np.asarray(rows_r))
        row_nbytes = 5 * np.dtype(np.asarray(buf).dtype).itemsize
        assert int(nb_k) == int(nb_r) == \
            int(np.sum(np.asarray(mask))) * row_nbytes
        # masked lanes must be zero (they feed a psum_scatter)
        got = np.asarray(rows_k)
        assert (got[np.asarray(mask) == 0] == 0).all()

    @pytest.mark.parametrize("dtype", [jnp.int32, jnp.float32])
    def test_scatter_rows_matches_oracle_with_collisions(self, dtype):
        """Duplicate target rows: the kernel's sequential lane-order
        application and the oracle's winner mask must agree bitwise —
        last writer wins, where 'last' is lane order."""
        rng = self._rng(2)
        buf = jnp.asarray(rng.integers(-99, 99, (6, 3))).astype(dtype)
        n = 10
        ix = jnp.asarray(rng.integers(0, 6, (n,)).astype(np.int32))
        vals = jnp.asarray(rng.integers(-99, 99, (n, 3))).astype(dtype)
        ap = jnp.asarray(rng.integers(0, 2, (n,)).astype(np.int32))
        wire = ap * jnp.asarray(rng.integers(0, 2, (n,)).astype(np.int32))
        out_k, nb_k = rdma.scatter_rows(buf, ix, vals, ap, wire)
        out_r, nb_r = rdma.scatter_rows(buf, ix, vals, ap, wire,
                                        force_ref=True)
        np.testing.assert_array_equal(np.asarray(out_k), np.asarray(out_r))
        row_nbytes = 3 * np.dtype(np.asarray(buf).dtype).itemsize
        assert int(nb_k) == int(nb_r) == \
            int(np.sum(np.asarray(wire))) * row_nbytes
        # python replay of the lane-order semantics
        exp = np.array(np.asarray(buf))
        for i in range(n):
            if int(np.asarray(ap)[i]):
                exp[int(np.asarray(ix)[i])] = np.asarray(vals)[i]
        np.testing.assert_array_equal(np.asarray(out_k), exp)

    def test_kernels_compose_under_vmap(self):
        """The verbs run the kernels inside a per-participant vmap trace
        (the tests' binding) — the kernels must vmap cleanly."""
        rng = self._rng(3)
        P, S, W, N = 4, 6, 3, 8
        buf = jnp.asarray(rng.integers(0, 99, (P, S, W)).astype(np.int32))
        ix = jnp.asarray(rng.integers(0, S, (P, N)).astype(np.int32))
        mask = jnp.asarray(rng.integers(0, 2, (P, N)).astype(np.int32))
        rows, nb = jax.vmap(lambda b, i, m: rdma.gather_rows(b, i, m))(
            buf, ix, mask)
        exp = np.where(np.asarray(mask)[..., None] != 0,
                       np.asarray(buf)[np.arange(P)[:, None],
                                       np.asarray(ix)], 0)
        np.testing.assert_array_equal(np.asarray(rows), exp)
        np.testing.assert_array_equal(
            np.asarray(nb), np.asarray(mask).sum(axis=1) * W * 4)

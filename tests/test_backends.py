"""Cross-backend conformance suite (DESIGN.md §14).

The swappable-backend contract has two halves, and this module pins both:

* **execution is bitwise-identical** — every channel driven through the
  ``active_message`` or ``pallas`` (remote-DMA kernel, §15) backend must
  produce exactly the results and final state leaves of the ``onesided``
  reference backend, window by window, on every variant (local / hashed
  placement / cached / lock-free);
* **only the cost model differs** — the TrafficLedger byte and round
  rows must follow each protocol's wire contract exactly: one-sided
  coalesced reads at 2·|row|·unique vs active-message (hdr+|row|)·lane
  RPCs vs pallas (desc+|row|)·unique descriptors, the write header tax,
  and the placed path's allocation round-trip (2 rounds one-sided and
  DMA, 0 when the decision ships with the op).  The pallas backend
  additionally files *measured* kernel bytes, pinned equal to its
  modeled rows here.

The alloc-fold regression (PR-5 carry-over) lives here too: a window
with no INSERT/MOVE lanes must keep the fast path's round shape — no
``.alloc`` round row, no speculative MOVE pre-read — and the reclaimed
rounds must be observable in the ledger totals.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (AM_HDR_BYTES, BACKENDS, DELETE, DMA_DESC_BYTES,
                        GET, INSERT, NOP, UPDATE, ActiveMessageBackend,
                        CollsBackend, KVStore, OneSidedBackend,
                        PallasDmaBackend, Ringbuffer, SharedQueue,
                        SharedRegion, get_backend, make_manager)

import test_kvstore as kvmod

P = 4
ALL_BACKENDS = ["onesided", "active_message", "pallas"]


def _assert_trees_equal(a, b, msg=""):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb), msg
    for i, (x, y) in enumerate(zip(la, lb)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=f"{msg} (leaf {i})")


# ------------------------------------------------------------ registry
class TestRegistry:
    def test_names_and_singletons(self):
        assert sorted(BACKENDS) == ["active_message", "onesided", "pallas"]
        assert get_backend("onesided") is BACKENDS["onesided"]
        assert get_backend("active_message") is BACKENDS["active_message"]
        assert get_backend("pallas") is BACKENDS["pallas"]
        assert isinstance(BACKENDS["onesided"], OneSidedBackend)
        assert isinstance(BACKENDS["active_message"], ActiveMessageBackend)
        assert isinstance(BACKENDS["pallas"], PallasDmaBackend)

    def test_resolution_chain(self):
        assert get_backend(None).name == "onesided"
        assert get_backend(None, default="active_message").name == \
            "active_message"
        inst = BACKENDS["active_message"]
        assert get_backend(inst) is inst
        assert get_backend(None, default=inst) is inst

    def test_unknown_backend_raises(self):
        with pytest.raises(ValueError, match="unknown colls backend"):
            get_backend("rdma_over_carrier_pigeon")

    def test_manager_env_default(self, monkeypatch):
        monkeypatch.setenv("REPRO_DEFAULT_BACKEND", "active_message")
        assert make_manager(P).backend.name == "active_message"
        monkeypatch.delenv("REPRO_DEFAULT_BACKEND")
        assert make_manager(P).backend.name == "onesided"

    def test_channels_inherit_and_override(self):
        mgr = make_manager(P, backend="active_message")
        assert mgr.backend.name == "active_message"
        kv = KVStore(None, "bk_inh", mgr, slots_per_node=4, value_width=2,
                     num_locks=2, index_capacity=32)
        assert kv.backend.name == "active_message"
        assert kv.rows_region.backend is kv.backend
        q = SharedQueue(None, "bq_inh", mgr, slots_per_node=2, width=1)
        assert q.backend.name == "active_message"
        rb = Ringbuffer(None, "br_inh", mgr, owner=0, capacity=4, width=2)
        assert rb.backend.name == "active_message"
        # per-channel override beats the manager default
        kv2 = KVStore(None, "bk_ovr", mgr, slots_per_node=4, value_width=2,
                      num_locks=2, index_capacity=32, backend="onesided")
        assert kv2.backend.name == "onesided"

    def test_alloc_rounds_contract(self):
        assert BACKENDS["onesided"].alloc_rounds == 2.0
        assert BACKENDS["active_message"].alloc_rounds == 0.0
        # DMA is one-sided: nothing ships to the home, the grant
        # round-trip stays
        assert BACKENDS["pallas"].alloc_rounds == 2.0

    def test_row_read_bytes_hooks(self):
        assert BACKENDS["onesided"].row_read_bytes(20) == 40.0
        assert BACKENDS["active_message"].row_read_bytes(20) == \
            AM_HDR_BYTES + 20
        assert BACKENDS["pallas"].row_read_bytes(20) == DMA_DESC_BYTES + 20

    def test_dma_desc_bytes_pins_kernel_layout(self):
        """The backend's literal descriptor constant cannot drift from
        the kernel module's actual descriptor layout."""
        from repro.kernels import remote_dma
        assert DMA_DESC_BYTES == remote_dma.DESC_BYTES \
            == remote_dma.DESC_WORDS * 4

    def test_abstract_base_raises(self):
        base = CollsBackend()
        with pytest.raises(NotImplementedError):
            base.read(None, 0, 0, "nodes")
        with pytest.raises(NotImplementedError):
            base.row_read_bytes(4)


# ------------------------------------------------- region conformance
class _RegionHarness:
    """One (manager, region, jitted program) per backend name."""
    _cache = {}

    def __new__(cls, backend):
        if backend not in cls._cache:
            cls._cache[backend] = super().__new__(cls)
            cls._cache[backend]._build(backend)
        return cls._cache[backend]

    def _build(self, backend):
        self.mgr = make_manager(P, backend=backend)
        self.rg = SharedRegion(None, f"breg_{backend}", self.mgr, slots=4,
                               item_shape=(3,), dtype=jnp.int32)

        @jax.jit
        def step(st, wt, wi, wv, rt, ri):
            def prog(st, wt, wi, wv, rt, ri):
                st, _ = self.rg.write_batch(st, wt, wi, wv)
                st, _ = self.rg.write(st, wt[0], wi[0] ^ 1, wv[0] + 1)
                vals, _ = self.rg.read_batch(st, rt, ri)
                one, _ = self.rg.read(st, rt[0], ri[0])
                return st, vals, one
            return self.mgr.runtime.run(prog, st, wt, wi, wv, rt, ri)

        self.step = step


def _region_script(seed):
    rng = np.random.default_rng(seed)
    wt = rng.integers(0, P, (P, 3)).astype(np.int32)
    wi = rng.integers(0, 4, (P, 3)).astype(np.int32)
    wv = rng.integers(-50, 50, (P, 3, 3)).astype(np.int32)
    rt = rng.integers(0, P, (P, 3)).astype(np.int32)
    ri = rng.integers(0, 4, (P, 3)).astype(np.int32)
    return tuple(map(jnp.asarray, (wt, wi, wv, rt, ri)))


@pytest.mark.parametrize("other", [b for b in ALL_BACKENDS
                                   if b != "onesided"])
def test_region_verbs_bitwise_across_backends(other):
    """Scalar and batched read/write on a shared region: same scripted
    traffic through each backend → identical outputs and final buffer
    vs the one-sided reference."""
    ha = _RegionHarness("onesided")
    hb = _RegionHarness(other)
    sta, stb = ha.rg.init_state(), hb.rg.init_state()
    for seed in range(4):
        script = _region_script(seed)
        sta, va, oa = ha.step(sta, *script)
        stb, vb, ob = hb.step(stb, *script)
        _assert_trees_equal((va, oa, sta), (vb, ob, stb),
                            f"{other} region script {seed}")


# --------------------------------------------------- kvstore conformance
def _kv_windows(n_rounds=4, B=2, seed=0, key_space=12):
    """Deterministic random windows with contention: duplicate keys,
    insert/delete churn, GET interleavings."""
    rng = np.random.default_rng(seed)
    codes = [NOP, GET, INSERT, INSERT, UPDATE, DELETE]
    windows = []
    for rnd in range(n_rounds):
        lanes = []
        for p in range(P):
            lane = []
            for b in range(B):
                op = codes[rng.integers(len(codes))]
                key = int(rng.integers(1, key_space + 1))
                lane.append((op, key, kvmod.v(key, rnd * B + b)))
            lanes.append(lane)
        windows.append(lanes)
    return windows


KV_VARIANTS = {
    "local": {},
    "hashed": {"placement": "hashed"},
    "cached": {"cache_slots": 8},
    "lockfree": {"lockfree": True},
    "reference": {"reference_impl": True},
}


class _KVBackendHarness:
    _cache = {}

    def __new__(cls, backend, variant):
        key = (backend, variant)
        if key not in cls._cache:
            cls._cache[key] = super().__new__(cls)
            cls._cache[key]._build(backend, variant)
        return cls._cache[key]

    def _build(self, backend, variant):
        self.mgr = make_manager(P, backend=backend)
        kw = dict(slots_per_node=8, value_width=2, num_locks=8,
                  index_capacity=64)
        kw.update(KV_VARIANTS[variant])
        self.kv = KVStore(None, f"bkv_{backend}_{variant}", self.mgr, **kw)
        self.step = jax.jit(lambda s, o, k, v: self.mgr.runtime.run(
            self.kv.op_window, s, o, k, v))


def _drive_kv(h, windows):
    st = h.kv.init_state()
    outs = []
    for w in windows:
        op = jnp.asarray([[o[0] for o in lane] for lane in w], jnp.int32)
        key = jnp.asarray([[o[1] for o in lane] for lane in w], jnp.uint32)
        val = jnp.asarray([[o[2] for o in lane] for lane in w], jnp.int32)
        st, res = h.step(st, op, key, val)
        outs.append(jax.tree.map(np.asarray, res))
    return st, outs


@pytest.mark.parametrize("other", [b for b in ALL_BACKENDS
                                   if b != "onesided"])
@pytest.mark.parametrize("variant", sorted(KV_VARIANTS))
def test_kvstore_windows_bitwise_across_backends(variant, other):
    """Every kvstore execution variant commits bit-identical per-window
    results AND bit-identical final state leaves under every backend —
    the conformance half of the §14 contract."""
    ha = _KVBackendHarness("onesided", variant)
    hb = _KVBackendHarness(other, variant)
    windows = _kv_windows(n_rounds=4, seed=3)
    sta, outs_a = _drive_kv(ha, windows)
    stb, outs_b = _drive_kv(hb, windows)
    for rnd, (ra, rb) in enumerate(zip(outs_a, outs_b)):
        _assert_trees_equal(ra, rb, f"{variant}/{other} window {rnd}")
    _assert_trees_equal(sta, stb, f"{variant}/{other} final state")


def test_kvstore_oracle_per_backend(backend):
    """The existing windowed oracle suite, parameterized over the
    backend fixture: linearization semantics hold under each protocol,
    not just cross-backend agreement."""
    mgr = make_manager(P, backend=backend)
    kv = KVStore(None, f"bkv_oracle_{backend}", mgr, slots_per_node=4,
                 value_width=2, num_locks=2, index_capacity=64)
    windows = _kv_windows(n_rounds=3, seed=11, key_space=8)
    kvmod.check_windows_against_oracle(windows, store_mgr=mgr, store=kv)


def test_kvstore_scheduled_matches_reference_per_backend(backend):
    """Per-backend executable-spec pinning: the scheduled store and the
    flat-scan reference store agree bitwise when both run on the SAME
    backend (the §6 pinning property is backend-independent)."""
    hs = _KVBackendHarness(backend, "local")
    hr = _KVBackendHarness(backend, "reference")
    windows = _kv_windows(n_rounds=3, seed=7, key_space=8)
    _, outs_s = _drive_kv(hs, windows)
    _, outs_r = _drive_kv(hr, windows)
    for rnd, (rs, rr) in enumerate(zip(outs_s, outs_r)):
        _assert_trees_equal(rs, rr, f"{backend} window {rnd}")


# ------------------------------------------------- queue / ring conformance
def test_queue_windows_bitwise_across_backends():
    """Windowed enqueue/dequeue through each backend matches the FIFO
    oracle-checked onesided baseline bitwise (grants, values, state)."""
    results = {}
    for bk in ALL_BACKENDS:
        mgr = make_manager(P, backend=bk)
        q = SharedQueue(None, f"bq_{bk}", mgr, slots_per_node=2, width=2)

        @jax.jit
        def step(st, ew, ev, dw, q=q, mgr=mgr):
            def prog(st, ew, ev, dw):
                st, g = q.enqueue_window(st, ev, ew)
                st, v, ok = q.dequeue_window(st, dw)
                return st, g, v, ok
            return mgr.runtime.run(prog, st, ew, ev, dw)

        rng = np.random.default_rng(5)
        st = q.init_state()
        outs = []
        for _ in range(5):
            ew = jnp.asarray(rng.random((P, 3)) < 0.7)
            dw = jnp.asarray(rng.random((P, 3)) < 0.6)
            ev = jnp.asarray(
                rng.integers(1, 999, (P, 3, 2)).astype(np.int32))
            st, g, v, ok = step(st, ew, ev, dw)
            outs.append(jax.tree.map(np.asarray, (g, v, ok)))
        results[bk] = (st, outs)
    sta, outs_a = results["onesided"]
    for bk in ALL_BACKENDS[1:]:
        stb, outs_b = results[bk]
        for rnd, (ra, rb) in enumerate(zip(outs_a, outs_b)):
            _assert_trees_equal(ra, rb, f"{bk} queue round {rnd}")
        _assert_trees_equal(sta, stb, f"{bk} queue final state")


def test_ringbuffer_windows_bitwise_across_backends():
    results = {}
    for bk in ALL_BACKENDS:
        mgr = make_manager(P, backend=bk)
        rb = Ringbuffer(None, f"brb_{bk}", mgr, owner=0, capacity=5,
                        width=3)

        @jax.jit
        def step(st, msgs, lens, preds, rb=rb, mgr=mgr):
            def prog(st, msgs, lens, preds):
                st, sent, _ = rb.publish_window(st, msgs, lens, preds)
                st, m, l, got, _f = rb.recv_window(st, 3)
                return st, sent, m, l, got
            return mgr.runtime.run(prog, st, msgs, lens, preds)

        rng = np.random.default_rng(9)
        st = rb.init_state()
        outs = []
        for _ in range(4):
            msgs = np.broadcast_to(
                rng.integers(1, 999, (3, 3)).astype(np.int32), (P, 3, 3))
            lens = np.broadcast_to(
                rng.integers(1, 4, (3,)).astype(np.int32), (P, 3))
            preds = np.broadcast_to(rng.random((3,)) < 0.8, (P, 3))
            st, sent, m, l, got = step(st, jnp.asarray(msgs.copy()),
                                       jnp.asarray(lens.copy()),
                                       jnp.asarray(preds.copy()))
            outs.append(jax.tree.map(np.asarray, (sent, m, l, got)))
        results[bk] = (st, outs)
    sta, outs_a = results["onesided"]
    for bk in ALL_BACKENDS[1:]:
        stb, outs_b = results[bk]
        for rnd, (ra, rb_) in enumerate(zip(outs_a, outs_b)):
            _assert_trees_equal(ra, rb_, f"{bk} ring round {rnd}")
        _assert_trees_equal(sta, stb, f"{bk} ring final state")


# ------------------------------------------------------------- cost model
ITEM_WORDS = 4                       # region item = 4 int32 = 16 bytes
ITEM_NBYTES = ITEM_WORDS * 4


class _CostHarness:
    """Ledger-enabled region per backend; jitted AFTER enable() so the
    trace carries the recording callbacks."""
    _cache = {}

    def __new__(cls, backend):
        if backend not in cls._cache:
            cls._cache[backend] = super().__new__(cls)
            cls._cache[backend]._build(backend)
        return cls._cache[backend]

    def _build(self, backend):
        self.mgr = make_manager(P, backend=backend)
        self.mgr.traffic.enable()
        self.rg = SharedRegion(None, f"bcost_{backend}", self.mgr, slots=4,
                               item_shape=(ITEM_WORDS,), dtype=jnp.int32)

        @jax.jit
        def read_step(st, tg, ix):
            return self.mgr.runtime.run(
                lambda s, t, i: self.rg.read_batch(s, t, i)[0], st, tg, ix)

        @jax.jit
        def write_step(st, tg, ix, vv):
            return self.mgr.runtime.run(
                lambda s, t, i, v: self.rg.write_batch(s, t, i, v)[0],
                st, tg, ix, vv)

        self.read_step, self.write_step = read_step, write_step

    def verb(self, suffix):
        return f"{self.rg.full_name}.{suffix}"


class TestCostModel:
    def _run_read(self, backend, tg):
        h = _CostHarness(backend)
        h.mgr.traffic.reset()
        ix = jnp.zeros((P, 3), jnp.int32)
        jax.block_until_ready(
            h.read_step(h.rg.init_state(), jnp.asarray(tg, jnp.int32), ix))
        jax.effects_barrier()     # ledger callbacks must land before asserts
        return h

    def test_read_bytes_coalesced_vs_per_rpc(self, backend):
        """3 duplicate remote lanes per participant: one-sided coalesces
        to ONE wire row (2·|row|·unique); active-message ships one
        (hdr+|row|) RPC per lane — the home sees each request."""
        tg = np.stack([np.full((3,), (p + 1) % P) for p in range(P)])
        h = self._run_read(backend, tg)
        got = h.mgr.traffic.summary()[h.verb("read_batch")]["bytes"]
        if backend == "onesided":
            assert got == 2.0 * ITEM_NBYTES * 1 * P
        elif backend == "pallas":
            # DMA coalesces too: one descriptor + one row per unique pair
            assert got == (DMA_DESC_BYTES + ITEM_NBYTES) * 1 * P
        else:
            assert got == (AM_HDR_BYTES + ITEM_NBYTES) * 3 * P

    def test_self_targeted_lanes_cost_zero(self, backend):
        """Locality discount holds under BOTH protocols: lanes targeting
        the local participant put nothing on the modeled wire."""
        tg = np.stack([np.full((3,), p) for p in range(P)])
        h = self._run_read(backend, tg)
        assert h.mgr.traffic.summary()[h.verb("read_batch")]["bytes"] == 0.0

    def test_read_rounds(self, backend):
        """Reads cost 2 rounds (request, response) under both protocols,
        and rounds are cluster-wide (recorded once, not once per
        participant)."""
        tg = np.stack([np.full((3,), (p + 1) % P) for p in range(P)])
        h = self._run_read(backend, tg)
        rounds = h.mgr.traffic.rounds_summary()
        assert rounds[h.verb("read_batch")]["rounds"] == 2.0
        assert h.mgr.traffic.total_rounds() == 2.0

    def test_write_bytes_header_tax_and_rounds(self, backend):
        """Remote writes: one-sided pushes |row| per lane; active-message
        pays the per-op header on the same lanes.  One round either way."""
        h = _CostHarness(backend)
        h.mgr.traffic.reset()
        tg = jnp.asarray(np.stack([np.full((3,), (p + 1) % P)
                                   for p in range(P)]), jnp.int32)
        ix = jnp.broadcast_to(jnp.arange(3, dtype=jnp.int32), (P, 3))
        vv = jnp.ones((P, 3, ITEM_WORDS), jnp.int32)
        jax.block_until_ready(h.write_step(h.rg.init_state(), tg, ix, vv))
        jax.effects_barrier()
        got = h.mgr.traffic.summary()[h.verb("write_batch")]["bytes"]
        if backend == "onesided":
            assert got == ITEM_NBYTES * 3 * P
        elif backend == "pallas":
            assert got == (DMA_DESC_BYTES + ITEM_NBYTES) * 3 * P
        else:
            assert got == (AM_HDR_BYTES + ITEM_NBYTES) * 3 * P
        assert h.mgr.traffic.rounds_summary()[
            h.verb("write_batch")]["rounds"] == 1.0

    def test_ring_publish_cost_model(self, backend):
        """Publish of n slots: one-sided 2·|slot|·n (push + counter
        read-back), active-message (hdr+|slot|)·n direct messages."""
        mgr = make_manager(P, backend=backend)
        mgr.traffic.enable()
        rb = Ringbuffer(None, f"brbc_{backend}", mgr, owner=0, capacity=8,
                        width=2)
        pub = jax.jit(lambda s, m, l: mgr.runtime.run(
            lambda st, mm, ll: rb.publish_window(st, mm, ll)[0], s, m, l))
        msgs = jnp.ones((P, 3, 2), jnp.int32)
        lens = jnp.full((P, 3), 2, jnp.int32)
        jax.block_until_ready(pub(rb.init_state(), msgs, lens))
        jax.effects_barrier()
        verb = f"{rb.full_name}.publish"
        got = mgr.traffic.summary()[verb]["bytes"]
        slot = rb.slot_nbytes
        if backend == "onesided":
            assert got == 2.0 * slot * 3
        elif backend == "pallas":
            assert got == (DMA_DESC_BYTES + slot) * 3
        else:
            assert got == (AM_HDR_BYTES + slot) * 3
        assert mgr.traffic.rounds_summary()[verb]["rounds"] == 1.0

    def test_pallas_measured_matches_modeled(self):
        """The §15 two-tier contract on the verb microbench: the bytes
        the DMA kernels *measure* (descriptors emitted + rows
        served/committed, counted from the masks that drive the copies)
        must equal the modeled (desc+row)·unique contract exactly — with
        duplicate lanes in the window, so the assertion also proves the
        descriptor block is built after leader election."""
        h = _CostHarness("pallas")
        h.mgr.traffic.reset()
        tg = jnp.asarray(np.stack([np.full((3,), (p + 1) % P)
                                   for p in range(P)]), jnp.int32)
        ix = jnp.zeros((P, 3), jnp.int32)          # 3 duplicate lanes
        jax.block_until_ready(h.read_step(h.rg.init_state(), tg, ix))
        vv = jnp.ones((P, 3, ITEM_WORDS), jnp.int32)
        ixw = jnp.broadcast_to(jnp.arange(3, dtype=jnp.int32), (P, 3))
        jax.block_until_ready(h.write_step(h.rg.init_state(), tg, ixw, vv))
        jax.effects_barrier()
        modeled = h.mgr.traffic.summary()
        measured = h.mgr.traffic.dma_summary()
        for suffix in ("read_batch", "write_batch"):
            verb = h.verb(suffix)
            assert measured[verb]["bytes"] == modeled[verb]["bytes"], suffix
        assert h.mgr.traffic.total_dma_bytes() == \
            (DMA_DESC_BYTES + ITEM_NBYTES) * 1 * P \
            + (DMA_DESC_BYTES + ITEM_NBYTES) * 3 * P

    def test_pallas_measured_tier_silent_on_other_backends(self, backend):
        """Only the DMA backend populates the measured tier."""
        h = self._run_read(backend, np.stack(
            [np.full((3,), (p + 1) % P) for p in range(P)]))
        if backend == "pallas":
            assert h.mgr.traffic.dma_counts
        else:
            assert not h.mgr.traffic.dma_counts


# ------------------------------------------- alloc fold (PR-5 carry-over)
class TestAllocFold:
    """No-allocation windows keep the fast path's round shape: the placed
    path's slot-allocation round-trip and speculative MOVE pre-read run
    only when the gathered schedule contains an INSERT/MOVE lane."""

    B = 2

    def _harness(self, backend):
        mgr = make_manager(P, backend=backend)
        mgr.traffic.enable()
        kv = KVStore(None, f"balloc_{backend}", mgr, slots_per_node=8,
                     value_width=2, num_locks=8, index_capacity=64,
                     placement="hashed")
        step = jax.jit(lambda s, o, k, v: mgr.runtime.run(
            kv.op_window, s, o, k, v))
        return mgr, kv, step

    def _window(self, step, st, opcode, keys):
        op = jnp.full((P, self.B), opcode, jnp.int32)
        key = jnp.asarray(keys, jnp.uint32)
        val = jnp.asarray([[kvmod.v(int(k), 1) for k in lane]
                           for lane in keys], jnp.int32)
        st, res = step(st, op, key, val)
        jax.block_until_ready(res)
        jax.effects_barrier()     # ledger callbacks must land before asserts
        return st

    def test_no_alloc_window_reclaims_alloc_rounds(self, backend):
        mgr, kv, step = self._harness(backend)
        keys = np.arange(1, P * self.B + 1).reshape(P, self.B)
        st = kv.init_state()

        mgr.traffic.reset()
        st = self._window(step, st, INSERT, keys)
        ins_rounds = mgr.traffic.rounds_summary()
        ins_total = mgr.traffic.total_rounds()
        alloc_verb = f"{kv.full_name}.alloc"
        move_verb = f"{kv.full_name}.move_read"
        # allocating windows pay the backend's grant round-trip...
        assert alloc_verb in ins_rounds
        bk = get_backend(backend)
        if bk.alloc_rounds:
            assert ins_rounds[alloc_verb]["rounds"] >= bk.alloc_rounds
            assert ins_rounds[alloc_verb]["rounds"] % bk.alloc_rounds == 0
        else:
            assert ins_rounds[alloc_verb]["rounds"] == 0.0
        reclaimable = ins_rounds[alloc_verb]["rounds"] + \
            ins_rounds.get(move_verb, {"rounds": 0.0})["rounds"]

        # ...an UPDATE-only window on the SAME keys (same lock/conflict
        # schedule → same service-round count) must skip both entirely
        mgr.traffic.reset()
        st = self._window(step, st, UPDATE, keys)
        upd_rounds = mgr.traffic.rounds_summary()
        upd_total = mgr.traffic.total_rounds()
        assert alloc_verb not in upd_rounds, \
            "no-allocation window still paid the allocation round-trip"
        assert move_verb not in upd_rounds, \
            "no-allocation window still issued the MOVE pre-read"
        assert move_verb not in mgr.traffic.summary(), \
            "no-allocation window still put MOVE pre-read bytes on the wire"
        assert ins_total - upd_total == pytest.approx(reclaimable), \
            (ins_total, upd_total, reclaimable)

    def test_get_only_window_keeps_fast_shape(self, backend):
        mgr, kv, step = self._harness(backend)
        keys = np.arange(1, P * self.B + 1).reshape(P, self.B)
        st = kv.init_state()
        st = self._window(step, st, INSERT, keys)
        mgr.traffic.reset()
        self._window(step, st, GET, keys)
        rounds = mgr.traffic.rounds_summary()
        assert f"{kv.full_name}.alloc" not in rounds
        assert f"{kv.full_name}.move_read" not in rounds


@pytest.mark.parametrize("backend", ALL_BACKENDS)
def test_ledger_totals_repeat_exactly(backend):
    """The ledger's callbacks run on several threads at once; the same
    window must count the same totals every time (an unguarded update
    would lose counts depending on thread timing)."""
    n = 8                     # more participants → more concurrent callbacks
    mgr = make_manager(n, backend=backend)
    mgr.traffic.enable()
    kv = KVStore(None, f"bledger_{backend}", mgr, slots_per_node=8,
                 value_width=2, num_locks=8, index_capacity=128,
                 placement="hashed")
    step = jax.jit(lambda s, o, k, v: mgr.runtime.run(kv.op_window, s, o,
                                                      k, v))
    keys = np.arange(1, 2 * n + 1).reshape(n, 2)
    op = jnp.full((n, 2), INSERT, jnp.int32)
    val = jnp.asarray([[kvmod.v(int(k), 1) for k in lane] for lane in keys],
                      jnp.int32)
    prev = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        totals = set()
        for _ in range(12):
            mgr.traffic.reset()
            _st, res = step(kv.init_state(), op, jnp.asarray(keys, jnp.uint32),
                            val)
            jax.block_until_ready(res)
            jax.effects_barrier()
            totals.add((mgr.traffic.total_rounds(), mgr.traffic.total_bytes(),
                        mgr.traffic.total_dma_bytes()))
    finally:
        sys.setswitchinterval(prev)
    assert len(totals) == 1, totals


# ------------------------------------------------------------ serving engine
def test_serving_engine_backend_knob():
    """The engine threads its backend into every channel and reports the
    §14 counters in stats()."""
    from repro.configs import get_smoke_config
    from repro.serving.engine import ServingEngine
    cfg = get_smoke_config("llama3.2-3b").replace(dtype="float32")
    eng = ServingEngine(cfg, max_batch=2, max_seq=32,
                        backend="active_message")
    assert eng.backend.name == "active_message"
    assert eng.pages.backend.name == "active_message"
    assert eng._row_read_bytes == AM_HDR_BYTES + 20
    stats = eng.stats()
    assert stats["backend"] == "active_message"
    assert "modeled_rounds" in stats and "rounds_by_verb" in stats


# --------------------------------------------------- env-default smoke
def test_env_default_backend_round_trips(monkeypatch):
    """REPRO_DEFAULT_BACKEND flips a whole stack without code changes —
    the knob the CI backend matrix turns."""
    monkeypatch.setenv("REPRO_DEFAULT_BACKEND", "active_message")
    mgr = make_manager(P)
    kv = KVStore(None, "benv_kv", mgr, slots_per_node=4, value_width=2,
                 num_locks=2, index_capacity=32)
    assert mgr.backend.name == "active_message"
    assert kv.backend.name == "active_message"
    assert kv.rows_region.backend.name == "active_message"

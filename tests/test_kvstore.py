"""KVStore correctness: paper §6 semantics + Appendix C linearizability,
checked against a sequential oracle over the induced linearization order
(GETs at their pre-round remote read; modifications in ticket order).

Windowed histories (``op_window``) replay against the same oracle in the
window-induced total order: GETs at the window start, mutations in
(participant, window slot) lexicographic order.  ``op_round`` — the public
B=1 wrapper — is additionally pinned bit-for-bit against the retained
scalar reference implementation on randomized traces."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (DELETE, GET, INSERT, NOP, UPDATE, KVStore,
                        make_manager)

P = 4
S = 4          # slots per node
W = 2          # value words
LOCKS = 2

mgr = make_manager(P)
kv = KVStore(None, "kv", mgr, slots_per_node=S, value_width=W,
             num_locks=LOCKS, index_capacity=64)


@jax.jit
def step(st, op, key, val):
    return mgr.runtime.run(kv.op_round, st, op, key, val)


@jax.jit
def ref_step(st, op, key, val):
    return mgr.runtime.run(kv._op_round_reference, st, op, key, val)


@jax.jit
def window_step(st, op, key, val):
    return mgr.runtime.run(kv.op_window, st, op, key, val)


def drive(rounds):
    """rounds: list of per-participant op lists [(op, key, value), ...]."""
    st = kv.init_state()
    outs = []
    for ops in rounds:
        op = jnp.asarray([o[0] for o in ops], jnp.int32)
        key = jnp.asarray([o[1] for o in ops], jnp.uint32)
        val = jnp.asarray([o[2] for o in ops], jnp.int32)
        st, res = step(st, op, key, val)
        outs.append(jax.tree.map(np.asarray, res))
    return st, outs


def drive_windows(windows, store_mgr=None, store=None, state=None):
    """windows: list of rounds; each round is a per-participant list of
    equal-length windows [(op, key, value), ...]."""
    skv = store or kv
    st = skv.init_state() if state is None else state
    wstep = window_step if store is None else jax.jit(
        lambda s, o, k, v: store_mgr.runtime.run(skv.op_window, s, o, k, v))
    outs = []
    for w in windows:
        op = jnp.asarray([[o[0] for o in lane] for lane in w], jnp.int32)
        key = jnp.asarray([[o[1] for o in lane] for lane in w], jnp.uint32)
        val = jnp.asarray([[o[2] for o in lane] for lane in w], jnp.int32)
        st, res = wstep(st, op, key, val)
        outs.append(jax.tree.map(np.asarray, res))
    return st, outs


class Oracle:
    """Sequential replay in the linearization order the channel induces."""

    def __init__(self, n_participants=P, slots=S):
        self.map = {}
        self.free = [slots] * n_participants
        self.loc = {}

    def _mod(self, p, op, key, val):
        """Apply one mutation at its linearization point; returns success."""
        if op == INSERT:
            if key not in self.map and self.free[p] > 0:
                self.map[key] = tuple(val)
                self.loc[key] = p
                self.free[p] -= 1
                return True
        elif op == UPDATE:
            if key in self.map:
                self.map[key] = tuple(val)
                return True
        elif op == DELETE:
            if key in self.map:
                del self.map[key]
                self.free[self.loc.pop(key)] += 1
                return True
        return False

    def apply_round(self, ops):
        pre = dict(self.map)
        results = [None] * len(ops)
        for p, (op, key, val) in enumerate(ops):
            if op == GET:
                results[p] = pre.get(key)
        for p, (op, key, val) in enumerate(ops):
            if op in (INSERT, UPDATE, DELETE):
                results[p] = self._mod(p, op, key, val)
        return results

    def apply_window(self, window):
        """Window-induced order: GETs at the window start; mutations in
        (participant, window slot) lexicographic order."""
        pre = dict(self.map)
        results = [[None] * len(lane) for lane in window]
        for p, lane in enumerate(window):
            for b, (op, key, val) in enumerate(lane):
                if op == GET:
                    results[p][b] = pre.get(key)
        for p, lane in enumerate(window):
            for b, (op, key, val) in enumerate(lane):
                if op in (INSERT, UPDATE, DELETE):
                    results[p][b] = self._mod(p, op, key, val)
        return results


def assert_lookup_pinned(store, store_mgr, st, keys=range(1, 33)):
    """Pin the O(PROBE) hash probe bit-for-bit against the O(C) flat scan
    on the store's current index state (found, pos, node, slot, ctr — all
    five lanes, including the pos-0 convention for missing keys)."""
    ks = jnp.asarray(list(keys), jnp.uint32)

    @jax.jit
    def both(st, ks):
        def prog(s, k):
            a = jax.vmap(lambda q: store._index_lookup_hash(s, q))(k)
            b = jax.vmap(lambda q: store._index_lookup_reference(s, q))(k)
            return a, b
        return store_mgr.runtime.run(prog, st, jnp.broadcast_to(
            ks, (store.P,) + ks.shape))

    a, b = both(st, ks)
    for la, lb in zip(a, b):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def check_windows_against_oracle(windows, store_mgr=None, store=None):
    skv, smgr = (store or kv), (store_mgr or mgr)
    _st, outs = drive_windows(windows, store_mgr=store_mgr, store=store)
    assert_lookup_pinned(skv, smgr, _st)
    oracle = Oracle(slots=skv.S)
    for rnd, (w, res) in enumerate(zip(windows, outs)):
        expect = oracle.apply_window(w)
        for p, lane in enumerate(w):
            for b, (op, key, val) in enumerate(lane):
                if op == NOP:
                    continue
                if op == GET:
                    exp = expect[p][b]
                    assert bool(res.found[p][b]) == (exp is not None), \
                        f"window {rnd} p{p}b{b} GET({key}) found mismatch"
                    if exp is not None:
                        np.testing.assert_array_equal(res.value[p][b], exp)
                else:
                    assert bool(res.found[p][b]) == expect[p][b], \
                        f"window {rnd} p{p}b{b} op{op}({key}) ok mismatch"


def check_against_oracle(rounds):
    _st, outs = drive(rounds)
    assert_lookup_pinned(kv, mgr, _st)
    oracle = Oracle()
    for rnd, (ops, res) in enumerate(zip(rounds, outs)):
        expect = oracle.apply_round(ops)
        for p, (op, key, val) in enumerate(ops):
            if op == NOP:
                continue
            if op == GET:
                exp = expect[p]
                assert bool(res.found[p]) == (exp is not None), \
                    f"round {rnd} p{p} GET({key}) found mismatch"
                if exp is not None:
                    np.testing.assert_array_equal(res.value[p], exp)
            else:
                assert bool(res.found[p]) == expect[p], \
                    f"round {rnd} p{p} op{op}({key}) ok mismatch"


def v(key, salt=0):
    return (int(key) * 10 + salt, int(key) * 100 + salt)


NOPR = (NOP, 1, (0, 0))


class TestKVStoreBasic:
    def test_insert_then_get(self):
        check_against_oracle([
            [(INSERT, 5, v(5)), NOPR, NOPR, NOPR],
            [NOPR, (GET, 5, v(0)), NOPR, NOPR],
        ])

    def test_get_missing_returns_empty(self):
        check_against_oracle([[NOPR, NOPR, (GET, 9, v(0)), NOPR]])

    def test_update_and_delete_lifecycle(self):
        check_against_oracle([
            [(INSERT, 3, v(3)), NOPR, NOPR, NOPR],
            [NOPR, (UPDATE, 3, v(3, 7)), NOPR, (GET, 3, v(0))],
            [(GET, 3, v(0)), NOPR, (DELETE, 3, v(0)), NOPR],
            [NOPR, (GET, 3, v(0)), NOPR, (UPDATE, 3, v(3, 9))],
        ])

    def test_concurrent_inserts_distinct_keys(self):
        check_against_oracle([
            [(INSERT, k, v(k)) for k in (1, 2, 3, 4)],
            [(GET, k, v(0)) for k in (4, 3, 2, 1)],
        ])

    def test_concurrent_insert_same_key_one_wins(self):
        check_against_oracle([
            [(INSERT, 7, v(7, 1)), (INSERT, 7, v(7, 2)),
             (INSERT, 7, v(7, 3)), NOPR],
            [(GET, 7, v(0)), NOPR, NOPR, NOPR],
        ])

    def test_same_round_insert_get_sees_pre_state(self):
        check_against_oracle([
            [(INSERT, 2, v(2)), (GET, 2, v(0)), NOPR, NOPR],
            [(GET, 2, v(0)), (DELETE, 2, v(0)), NOPR, NOPR],
        ])

    def test_contended_lock_stripe_serializes(self):
        # keys 2 and 4 share lock stripe (2 % 2 == 4 % 2)
        check_against_oracle([
            [(INSERT, 2, v(2)), (INSERT, 4, v(4)),
             (UPDATE, 2, v(2, 5)), (DELETE, 4, v(0))],
            [(GET, 2, v(0)), (GET, 4, v(0)), NOPR, NOPR],
        ])

    def test_capacity_exhaustion_fails_insert(self):
        rounds = []
        # participant 0 inserts S+1 keys mapping to its own slots
        for i in range(S + 1):
            rounds.append([(INSERT, 10 + i, v(10 + i)), NOPR, NOPR, NOPR])
        check_against_oracle(rounds)

    def test_slot_reuse_after_delete(self):
        check_against_oracle([
            [(INSERT, 11, v(11)), NOPR, NOPR, NOPR],
            [(DELETE, 11, v(0)), NOPR, NOPR, NOPR],
            [(INSERT, 13, v(13)), NOPR, NOPR, NOPR],
            [(GET, 11, v(0)), (GET, 13, v(0)), NOPR, NOPR],
        ])


class TestAppendixCValidation:
    """Direct checks of the read-path case analysis (Appendix C)."""

    def _seed_state(self):
        st = kv.init_state()
        op = jnp.asarray([INSERT, NOP, NOP, NOP], jnp.int32)
        key = jnp.asarray([5, 1, 1, 1], jnp.uint32)
        val = jnp.asarray([v(5), (0, 0), (0, 0), (0, 0)], jnp.int32)
        st, _ = step(st, op, key, val)
        return st

    def _get5(self, st):
        op = jnp.asarray([NOP, GET, NOP, NOP], jnp.int32)
        key = jnp.asarray([1, 5, 1, 1], jnp.uint32)
        val = jnp.zeros((P, W), jnp.int32)
        _st, res = step(st, op, key, val)
        return jax.tree.map(np.asarray, res)

    def test_case1_valid_read_returns_value(self):
        res = self._get5(self._seed_state())
        assert res.found[1]
        np.testing.assert_array_equal(res.value[1], v(5))

    def test_case2_torn_row_retries_then_empty(self):
        st = self._seed_state()
        # corrupt the stored row at its host (inserter was participant 0):
        buf = np.asarray(st.rows.buf).copy()
        slot = np.nonzero(buf[0, :, W + 1] == 1)[0][0]  # valid row at node 0
        buf[0, slot, 0] ^= 0x5A5A  # tear the payload, checksum now stale
        st = st._replace(rows=st.rows._replace(buf=jnp.asarray(buf)))
        res = self._get5(st)
        assert not res.found[1]
        assert res.retries[1] == 3  # MAX_GET_RETRIES exhausted

    def test_case3_invalid_bit_returns_empty(self):
        st = self._seed_state()
        buf = np.asarray(st.rows.buf).copy()
        slot = np.nonzero(buf[0, :, W + 1] == 1)[0][0]
        row = buf[0, slot].copy()
        row[W + 1] = 0  # unset valid bit, re-checksum (a mid-insert snapshot)
        from repro.core.ownedvar import checksum as cks
        row[W + 2] = np.asarray(
            jax.lax.bitcast_convert_type(cks(jnp.asarray(row[:W + 2])),
                                         jnp.int32))
        buf[0, slot] = row
        st = st._replace(rows=st.rows._replace(buf=jnp.asarray(buf)))
        res = self._get5(st)
        assert not res.found[1]
        assert res.retries[1] == 0  # clean read, EMPTY by case 3

    def test_case4_counter_mismatch_returns_empty(self):
        from repro.core.kvstore import IDX_CTR, IDX_KEY
        st = self._seed_state()
        # stale local index at participant 1: ctr behind the slot's counter
        idx = np.asarray(st.idx).copy()
        pos = np.nonzero(idx[1, :, IDX_KEY] == 5)[0][0]
        idx[1, pos, IDX_CTR] -= 1
        st = st._replace(idx=jnp.asarray(idx))
        res = self._get5(st)
        assert not res.found[1]
        assert res.retries[1] == 0


class TestKVStoreRandomized:
    @pytest.mark.parametrize("seed", range(6))
    def test_random_batches_match_oracle(self, seed):
        rng = np.random.default_rng(seed)
        keys = list(range(1, 7))
        rounds = []
        for rnd in range(6):
            ops = []
            for p in range(P):
                op = int(rng.choice([NOP, GET, INSERT, UPDATE, DELETE],
                                    p=[.1, .3, .3, .15, .15]))
                key = int(rng.choice(keys))
                ops.append((op, key, v(key, rnd)))
            rounds.append(ops)
        check_against_oracle(rounds)


class TestWindowedOps:
    """op_window linearizability: windowed histories vs the oracle replayed
    in the window-induced total order."""

    def test_window_insert_then_get_roundtrip(self):
        check_windows_against_oracle([
            [[(INSERT, 1, v(1)), (INSERT, 2, v(2))],
             [(INSERT, 3, v(3)), (INSERT, 4, v(4))],
             [NOPR, NOPR], [NOPR, NOPR]],
            [[(GET, 4, v(0)), (GET, 3, v(0))],
             [(GET, 2, v(0)), (GET, 9, v(0))],
             [(GET, 1, v(0)), NOPR], [NOPR, (GET, 2, v(0))]],
        ])

    def test_window_gets_linearize_at_window_start(self):
        # the UPDATE lands within the window; every GET lane (any slot,
        # any participant) still observes the pre-window value.
        check_windows_against_oracle([
            [[(INSERT, 5, v(5))], [NOPR], [NOPR], [NOPR]],
            [[(UPDATE, 5, v(5, 9)), (GET, 5, v(0))],
             [(GET, 5, v(0)), (GET, 5, v(0))], [NOPR, NOPR], [NOPR, NOPR]],
            [[(GET, 5, v(0))], [NOPR], [NOPR], [NOPR]],
        ])

    def test_delete_insert_same_key_one_window(self):
        # within one participant's window: window order (delete, then
        # re-insert) — both succeed, slot recycled through the free stack.
        check_windows_against_oracle([
            [[(INSERT, 7, v(7))], [NOPR], [NOPR], [NOPR]],
            [[(DELETE, 7, v(0)), (INSERT, 7, v(7, 2))],
             [NOPR, NOPR], [NOPR, NOPR], [NOPR, NOPR]],
            [[(GET, 7, v(0))], [NOPR], [NOPR], [NOPR]],
        ])

    def test_cross_participant_same_key_participant_then_window_order(self):
        # key 6 absent.  p0 INSERTs it at window slot 1; p1 DELETEs it at
        # window slot 0.  Per-lock FIFO is (participant, slot) order, so
        # p0's (later-slot) insert precedes p1's (earlier-slot) delete —
        # both succeed.  A window-major order would fail both.
        check_windows_against_oracle([
            [[NOPR, (INSERT, 6, v(6))],
             [(DELETE, 6, v(0)), NOPR], [NOPR, NOPR], [NOPR, NOPR]],
            [[(GET, 6, v(0))], [NOPR], [NOPR], [NOPR]],
        ])

    @pytest.mark.parametrize("seed", range(4))
    def test_random_windows_match_oracle(self, seed):
        rng = np.random.default_rng(100 + seed)
        keys = list(range(1, 7))
        B = 3
        windows = []
        for rnd in range(4):
            w = []
            for p in range(P):
                lane = []
                for b in range(B):
                    op = int(rng.choice(
                        [NOP, GET, INSERT, UPDATE, DELETE],
                        p=[.1, .3, .3, .15, .15]))
                    key = int(rng.choice(keys))
                    lane.append((op, key, v(key, rnd * B + b)))
                w.append(lane)
            windows.append(w)
        check_windows_against_oracle(windows)

    def test_window_equals_op_round_sequence(self):
        """On histories whose windows have no cross-lane conflicts (each key
        mutated by one lane; GET keys unmutated in that window) and no
        capacity pressure (a window-mode insert allocates before a
        concurrent delete's slot GC lands), op_window is observably
        equivalent to running the window slots as successive op_rounds."""
        emgr = make_manager(P)
        ekv = KVStore(None, "kv_equiv", emgr, slots_per_node=32,
                      value_width=W, num_locks=LOCKS, index_capacity=256)
        estep = jax.jit(lambda s, o, k, vv: emgr.runtime.run(
            ekv.op_round, s, o, k, vv))
        rng = np.random.default_rng(7)
        B = 3
        windows = []
        live = set()
        for rnd in range(4):
            pool = list(range(1, 20))
            rng.shuffle(pool)
            w = []
            for p in range(P):
                lane = []
                for b in range(B):
                    key = pool.pop()   # unique key per lane in this window
                    if key in live:
                        op = int(rng.choice([GET, UPDATE, DELETE],
                                            p=[.4, .4, .2]))
                        if op == DELETE:
                            live.discard(key)
                    else:
                        op = int(rng.choice([GET, INSERT], p=[.3, .7]))
                        if op == INSERT:
                            live.add(key)
                    lane.append((op, key, v(key, rnd * B + b)))
                w.append(lane)
            windows.append(w)

        st_w, outs_w = drive_windows(windows, store_mgr=emgr, store=ekv)
        # replay the same histories as B successive op_rounds per window
        st_s = ekv.init_state()
        outs_s = []
        for w in windows:
            per_lane = []
            for b in range(B):
                ops = [lane[b] for lane in w]
                op = jnp.asarray([o[0] for o in ops], jnp.int32)
                key = jnp.asarray([o[1] for o in ops], jnp.uint32)
                val = jnp.asarray([o[2] for o in ops], jnp.int32)
                st_s, res = estep(st_s, op, key, val)
                per_lane.append(jax.tree.map(np.asarray, res))
            outs_s.append(per_lane)
        for rnd, (w, res_w, res_s) in enumerate(
                zip(windows, outs_w, outs_s)):
            for p, lane in enumerate(w):
                for b, (op, key, val) in enumerate(lane):
                    if op == NOP:
                        continue
                    assert bool(res_w.found[p][b]) == \
                        bool(res_s[b].found[p]), \
                        f"window {rnd} p{p}b{b} op{op}({key})"
                    np.testing.assert_array_equal(res_w.value[p][b],
                                                  res_s[b].value[p])
        # both executions agree on the final logical contents
        probe = jnp.broadcast_to(
            jnp.arange(1, 21, dtype=jnp.uint32), (P, 20))

        @jax.jit
        def probe_all(st, keys):
            _st, v, f = emgr.runtime.run(lambda s, k: ekv.get_batch(s, k),
                                         st, keys)
            return v, f

        vw, fw = probe_all(st_w, probe)
        vs, fs = probe_all(st_s, probe)
        np.testing.assert_array_equal(np.asarray(fw), np.asarray(fs))
        np.testing.assert_array_equal(np.asarray(vw), np.asarray(vs))

    @pytest.mark.parametrize("seed", range(3))
    def test_op_round_bitidentical_to_reference(self, seed):
        """Acceptance regression: op_window with B=1 (== public op_round)
        is bit-identical to the retained scalar reference implementation —
        full state pytree and results — on randomized mixed-op traces."""
        rng = np.random.default_rng(40 + seed)
        keys = list(range(1, 7))
        st_a = st_b = kv.init_state()
        for rnd in range(6):
            ops = []
            for p in range(P):
                op = int(rng.choice([NOP, GET, INSERT, UPDATE, DELETE],
                                    p=[.1, .3, .3, .15, .15]))
                key = int(rng.choice(keys))
                ops.append((op, key, v(key, rnd)))
            op = jnp.asarray([o[0] for o in ops], jnp.int32)
            key = jnp.asarray([o[1] for o in ops], jnp.uint32)
            val = jnp.asarray([o[2] for o in ops], jnp.int32)
            st_a, res_a = step(st_a, op, key, val)
            st_b, res_b = ref_step(st_b, op, key, val)
            for la, lb in zip(jax.tree.leaves(st_a), jax.tree.leaves(st_b)):
                np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
            for la, lb in zip(jax.tree.leaves(res_a._asdict()),
                              jax.tree.leaves(res_b._asdict())):
                np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


class TestWindowEdgeCases:
    def test_insert_window_exceeds_free_stack(self):
        # p0 inserts S+2 distinct keys in one window: exactly S land (the
        # earliest queue positions), the rest report found=False.
        B = S + 2
        w = [[(INSERT, 10 + b, v(10 + b)) for b in range(B)]] + \
            [[NOPR] * B for _ in range(P - 1)]
        st, outs = drive_windows([w])
        found = outs[0].found[0]
        assert found.sum() == S
        assert not found[S:].any(), "capacity failures are the excess ops"
        # the survivors are readable; the failed keys are absent
        gets = [[(GET, 10 + b, v(0)) for b in range(B)]] + \
            [[NOPR] * B for _ in range(P - 1)]
        _st2, outs2 = drive_windows([w, gets])
        np.testing.assert_array_equal(outs2[1].found[0], found)

    def test_index_overflow_reports_failure_and_latches(self):
        smgr = make_manager(P)
        skv = KVStore(None, "kv_tinyidx", smgr, slots_per_node=S,
                      value_width=W, num_locks=LOCKS, index_capacity=2)
        w = [[(INSERT, k, v(k)) for k in (1, 2, 3)]] + \
            [[NOPR] * 3 for _ in range(P - 1)]
        st, outs = drive_windows([w], store_mgr=smgr, store=skv)
        found = outs[0].found[0]
        np.testing.assert_array_equal(found, [True, True, False])
        assert bool(np.asarray(st.idx_overflow).all()), \
            "overflow latches on every participant's index replica"
        # the un-indexed insert returned its slot to the inserter's stack
        np.testing.assert_array_equal(np.asarray(st.free_top),
                                      [S - 2] + [S] * (P - 1))

    def test_delete_and_reinsert_full_stack_same_window(self):
        # fill p0 completely, then delete one key and insert a fresh one in
        # the same window (delete's lock FIFO slot precedes the insert):
        # the freed slot is recycled within the window.
        fill = [[(INSERT, 10 + b, v(10 + b)) for b in range(S)]] + \
            [[NOPR] * S for _ in range(P - 1)]
        w2 = [[(DELETE, 10, v(0)), (INSERT, 30, v(30))]] + \
            [[NOPR, NOPR] for _ in range(P - 1)]
        probe = [[(GET, 10, v(0)), (GET, 30, v(0))]] + \
            [[NOPR, NOPR] for _ in range(P - 1)]
        _st, outs = drive_windows([fill, w2, probe])
        np.testing.assert_array_equal(outs[1].found[0], [True, True])
        np.testing.assert_array_equal(outs[2].found[0], [False, True])


class TestRowEncoding:
    """Property tests for encode_row/decode_row (deterministic mirror of the
    hypothesis suite in test_properties.py, so they run without dev deps)."""

    @pytest.mark.parametrize("seed", range(4))
    def test_checksum_catches_any_single_word_tear(self, seed):
        rng = np.random.default_rng(seed)
        payload = rng.integers(-2**31, 2**31 - 1, size=W, dtype=np.int64)
        row = np.asarray(kv.encode_row(
            jnp.asarray(payload, jnp.int32),
            jnp.uint32(rng.integers(0, 2**32, dtype=np.uint64)),
            bool(rng.integers(0, 2))))
        _p, _c, _v, ok = kv.decode_row(jnp.asarray(row))
        assert bool(ok), "untorn row must validate"
        for pos in range(W + 2):           # any body word
            delta = int(rng.integers(1, 2**31 - 1))
            torn = row.copy()
            torn[pos] = np.int32(np.int64(torn[pos]) ^ delta)
            if np.array_equal(torn, row):
                continue
            _p, _c, _v, ok = kv.decode_row(jnp.asarray(torn))
            assert not bool(ok), f"tear at word {pos} must break checksum"

    def test_decode_case_analysis_elementwise(self):
        """Appendix C cases over a batched row set, vmapped elementwise:
        clean+valid, clean+invalid (mid-insert/post-delete), torn."""
        val = jnp.asarray(v(3), jnp.int32)
        rows = jnp.stack([
            kv.encode_row(val, jnp.uint32(5), True),    # case 1: valid
            kv.encode_row(val, jnp.uint32(5), False),   # case 3: invalid bit
            kv.encode_row(val, jnp.uint32(4), True),    # case 4: stale ctr
            kv.encode_row(val, jnp.uint32(5), True).at[0].add(1),  # case 2
        ])
        payload, ctr, valid, ok = jax.vmap(kv.decode_row)(rows)
        np.testing.assert_array_equal(np.asarray(valid),
                                      [True, False, True, True])
        np.testing.assert_array_equal(np.asarray(ok),
                                      [True, True, True, False])
        # index holds ctr=5: the GET-level accept mask is found only for 0
        accept = np.asarray(ok) & np.asarray(valid) & \
            (np.asarray(ctr) == 5)
        np.testing.assert_array_equal(accept, [True, False, False, False])
        np.testing.assert_array_equal(np.asarray(payload)[0], v(3))


def _np_hash32(x):
    """Numpy mirror of kvstore._hash_u32 (lowbias32), for crafting keys."""
    x = np.asarray(x, np.uint64)
    x = (x ^ (x >> np.uint64(16))) & np.uint64(0xFFFFFFFF)
    x = (x * np.uint64(0x7FEB352D)) & np.uint64(0xFFFFFFFF)
    x = (x ^ (x >> np.uint64(15))) & np.uint64(0xFFFFFFFF)
    x = (x * np.uint64(0x846CA68B)) & np.uint64(0xFFFFFFFF)
    return ((x ^ (x >> np.uint64(16))) & np.uint64(0xFFFFFFFF)).astype(
        np.uint32)


def _keys_in_bucket(C, bucket, n, start=1):
    """First n keys ≥ start whose hash lands in ``bucket`` (mod C)."""
    out, k = [], start
    while len(out) < n:
        if int(_np_hash32(k)) % C == bucket:
            out.append(k)
        k += 1
    return out


def _recs(*entries):
    """Tracker records from (kind, key, node, slot, ctr) tuples."""
    r = np.zeros((len(entries), 5), np.int32)
    for i, (kind, key, node, slot, ctr) in enumerate(entries):
        r[i] = [kind, key, node, slot, ctr]
    return r


class _ApplyHarness:
    """Drive _apply_tracker variants directly (unit level, vmap binding)."""

    def __init__(self, C=8, S=16, probe=None):
        self.mgr = make_manager(P)
        self.kv = KVStore(None, f"kv_apply_c{C}_{probe}_{id(self)}",
                          self.mgr, slots_per_node=S, value_width=W,
                          num_locks=LOCKS, index_capacity=C,
                          index_max_probe=probe)
        self._vec = jax.jit(lambda s, r: self.mgr.runtime.run(
            self.kv._apply_tracker_vectorized, s, r))
        self._seq = jax.jit(lambda s, r: self.mgr.runtime.run(
            self.kv._apply_tracker_reference, s, r))

    def init(self):
        return self.kv.init_state()

    def apply(self, st, recs_np, variant="vec"):
        recs = jnp.asarray(np.broadcast_to(recs_np, (P,) + recs_np.shape))
        fn = self._vec if variant == "vec" else self._seq
        st, applied = fn(st, recs)
        return st, np.asarray(applied)[0]

    def lookup(self, st, keys, impl="hash"):
        ks = jnp.broadcast_to(jnp.asarray(keys, jnp.uint32),
                              (P, len(keys)))
        fn = {"hash": self.kv._index_lookup_hash,
              "ref": self.kv._index_lookup_reference}[impl]

        @jax.jit
        def run(st, ks):
            return self.mgr.runtime.run(
                lambda s, k: jax.vmap(lambda q: fn(s, q))(k), st, ks)

        out = run(st, ks)
        return jax.tree.map(lambda x: np.asarray(x)[0], out)


class TestHashIndex:
    """Unit tests of the open-addressing index through the tracker-apply
    path, each cross-checked bit-for-bit against _index_lookup_reference."""

    def _pin(self, h, st, keys):
        a = h.lookup(st, keys, "hash")
        b = h.lookup(st, keys, "ref")
        for la, lb in zip(a, b):
            np.testing.assert_array_equal(la, lb)

    def test_collision_chain_probes_through(self):
        C = 8
        h = _ApplyHarness(C=C)
        ks = _keys_in_bucket(C, 3, 3)       # three keys, same bucket
        st, applied = h.apply(h.init(), _recs(
            *[(1, k, i % P, i, 1) for i, k in enumerate(ks)]))
        assert applied.all()
        found, _pos, node, slot, _ctr = h.lookup(st, ks)
        assert found.all(), "all chain members reachable through the chain"
        np.testing.assert_array_equal(slot, np.arange(len(ks)))
        self._pin(h, st, ks + [99, 100])

    def test_probe_wraparound(self):
        C = 8
        h = _ApplyHarness(C=C)
        # fill the tail buckets so a chain starting near C-1 must wrap
        ks = _keys_in_bucket(C, C - 1, 3)
        st, applied = h.apply(h.init(), _recs(
            *[(1, k, 0, i, 1) for i, k in enumerate(ks)]))
        assert applied.all()
        pos = h.lookup(st, ks)[1]
        assert (pos < C).all() and pos[0] == C - 1 and (pos[1:] < C - 1).all(), \
            "chain wrapped past C-1 to the front of the table"
        found = h.lookup(st, ks)[0]
        assert found.all()
        self._pin(h, st, ks)

    def test_delete_reinsert_through_tombstones(self):
        C = 8
        h = _ApplyHarness(C=C)
        k1, k2, k3 = _keys_in_bucket(C, 5, 3)
        st, _ = h.apply(h.init(), _recs((1, k1, 0, 0, 1), (1, k2, 1, 1, 1)))
        # delete the chain head: k2 must stay reachable (tombstone, not
        # EMPTY, so the probe does not terminate early)
        st, applied = h.apply(st, _recs((2, k1, 0, 0, 1)))
        assert applied.all()
        found, _pos, _n, slot, _c = h.lookup(st, [k1, k2])
        np.testing.assert_array_equal(found, [False, True])
        # a fresh insert reclaims the tombstone at the chain head
        st, applied = h.apply(st, _recs((1, k3, 2, 2, 1)))
        assert applied.all()
        found, pos3, _n, slot3, _c = h.lookup(st, [k3])
        assert found[0] and pos3[0] == int(_np_hash32(k1)) % C, \
            "reinsert through the tombstone reclaims the freed position"
        self._pin(h, st, [k1, k2, k3])

    def test_load_factor_one_overflow_latches(self):
        C = 4
        h = _ApplyHarness(C=C)      # PROBE == C: window covers the table
        st, applied = h.apply(h.init(), _recs(
            *[(1, 10 + i, 0, i, 1) for i in range(C)]))
        assert applied.all(), "C inserts fill the table to load factor 1"
        assert not np.asarray(st.idx_overflow).any()
        st, applied = h.apply(st, _recs((1, 99, 0, C, 1)))
        assert not applied.any(), "insert into a full table fails"
        assert np.asarray(st.idx_overflow).all(), \
            "overflow latches on every participant's replica"
        # the table is unchanged and still fully readable
        found = h.lookup(st, [10 + i for i in range(C)])[0]
        assert found.all()
        self._pin(h, st, [10 + i for i in range(C)] + [99])

    def test_bounded_probe_overflow_before_capacity(self):
        # PROBE < C: a clustered window can overflow while the table still
        # has free positions elsewhere — the documented bounded-probe trade
        C, PROBE = 16, 4
        h = _ApplyHarness(C=C, probe=PROBE)
        ks = _keys_in_bucket(C, 7, PROBE + 1)
        st, applied = h.apply(h.init(), _recs(
            *[(1, k, 0, i, 1) for i, k in enumerate(ks)]))
        np.testing.assert_array_equal(applied, [True] * PROBE + [False])
        assert np.asarray(st.idx_overflow).all()

    # C = 16, PROBE = 4: entry 0 holds a key of its own; bucket 5 holds a
    # full window with a tombstone (k2 deleted), bucket 11 a full window
    # of USED entries; bucket 2 is EMPTY.
    _LC, _LPROBE = 16, 4

    @pytest.fixture(scope="class")
    def tombstoned(self):
        C = self._LC
        h = _ApplyHarness(C=C, probe=self._LPROBE)
        z = _keys_in_bucket(C, 0, 1)[0]
        k = _keys_in_bucket(C, 5, 5)
        m = _keys_in_bucket(C, 11, 5)
        st, applied = h.apply(h.init(), _recs(
            (1, z, 3, 9, 7),
            *[(1, key, i % P, 10 + i, 20 + i) for i, key in enumerate(k[:4])],
            *[(1, key, i % P, 30 + i, 40 + i) for i, key in enumerate(m[:4])]))
        assert applied.all()
        st, applied = h.apply(st, _recs((2, k[1], 1, 11, 21)))
        assert applied.all()
        keys = {"found": k[0], "after_tombstone": k[2], "deleted": k[1],
                "missing_tombstoned_window": k[4],
                "found_full_window_end": m[3], "missing_full_window": m[4],
                "missing_empty": _keys_in_bucket(C, 2, 1)[0]}
        return h, st, keys

    @pytest.mark.parametrize("case", [
        "found", "after_tombstone", "deleted", "missing_tombstoned_window",
        "found_full_window_end", "missing_full_window", "missing_empty"])
    def test_lookup_entry_from_probe_window(self, tombstoned, case):
        """The hash lookup reads node, slot and ctr from its probe window
        and answers as a re-read of ``idx[pos]`` does (entry 0 on a miss),
        and as the flat scan does."""
        h, st, keys = tombstoned
        key = keys[case]
        idx = np.asarray(st.idx)[0]
        C, PROBE = self._LC, self._LPROBE
        want_found, want_pos = False, 0
        for j in range(PROBE):
            p = (int(_np_hash32(key)) % C + j) % C
            if idx[p, 0] == 0:                  # EMPTY ends the chain
                break
            if idx[p, 0] == 1 and idx[p, 1] == key:
                want_found, want_pos = True, p
                break
        assert want_found == case.startswith(("found", "after"))
        found, pos, node, slot, ctr = h.lookup(st, [key])
        assert bool(found[0]) == want_found
        assert int(pos[0]) == want_pos
        np.testing.assert_array_equal(
            [int(node[0]), int(slot[0]), int(ctr[0])],
            idx[want_pos, 2:5].astype(np.int64))
        if not want_found:
            assert (int(node[0]), int(slot[0]), int(ctr[0])) == (3, 9, 7)
        self._pin(h, st, [key])


class TestTrackerApplyEquivalence:
    """Vectorized wave scheduler vs the sequential reference sweep on
    adversarial same-key record chains: same applied flags, same logical
    key → (node, slot, ctr) mapping (via the flat scan, which is layout-
    agnostic), same free-stack effects, same overflow latch."""

    def _check(self, recs_np, C=8, S=16, hv=None, hs=None):
        hv = hv or _ApplyHarness(C=C, S=S)
        hs = hs or _ApplyHarness(C=C, S=S)
        st_v, app_v = hv.apply(hv.init(), recs_np, "vec")
        st_s, app_s = hs.apply(hs.init(), recs_np, "seq")
        np.testing.assert_array_equal(app_v, app_s)
        keys = sorted(set(int(r[1]) for r in recs_np)) + [999]
        lv = hv.lookup(st_v, keys, "ref")
        ls = hs.lookup(st_s, keys, "ref")
        # logical equality: found everywhere; node/slot/ctr wherever found
        # (positions may differ — hash vs flat placement policies — and a
        # missing key's pos-0 row is layout junk in both)
        np.testing.assert_array_equal(lv[0], ls[0], err_msg="found")
        fnd = np.asarray(lv[0], bool)
        for name, a, b in zip("node slot ctr".split(), lv[2:], ls[2:]):
            np.testing.assert_array_equal(np.asarray(a)[fnd],
                                          np.asarray(b)[fnd], err_msg=name)
        np.testing.assert_array_equal(np.asarray(st_v.free_top),
                                      np.asarray(st_s.free_top))
        np.testing.assert_array_equal(np.asarray(st_v.free_stack),
                                      np.asarray(st_s.free_stack))
        np.testing.assert_array_equal(np.asarray(st_v.idx_overflow),
                                      np.asarray(st_s.idx_overflow))

    def test_same_key_insert_delete_insert_chain(self):
        self._check(_recs((1, 7, 0, 0, 1), (2, 7, 0, 0, 1),
                          (1, 7, 1, 3, 2)))

    def test_interleaved_chains_and_distinct_keys(self):
        self._check(_recs(
            (1, 5, 0, 0, 1), (1, 6, 1, 1, 1), (2, 5, 0, 0, 1),
            (1, 5, 2, 2, 2), (2, 6, 1, 1, 1), (1, 8, 3, 3, 1),
            (2, 8, 3, 3, 1), (1, 6, 0, 4, 2)))

    def test_delete_miss_and_dead_records(self):
        self._check(_recs((0, 1, 0, 0, 0), (2, 42, 0, 0, 1),
                          (1, 3, 0, 1, 1), (0, 2, 0, 0, 0),
                          (2, 3, 0, 1, 1)))

    def test_host_slot_gc_order_matches(self):
        # multiple deletes hosted at different nodes: free-stack pushes in
        # record order at each host
        recs = _recs(*[(1, 10 + i, i % P, i, 1) for i in range(8)])
        hv, hs = _ApplyHarness(C=32), _ApplyHarness(C=32)
        st_v, _ = hv.apply(hv.init(), recs, "vec")
        st_s, _ = hs.apply(hs.init(), recs, "seq")
        dels = _recs(*[(2, 10 + i, i % P, i, 1) for i in (5, 1, 3, 7)])
        st_v, av = hv.apply(st_v, dels, "vec")
        st_s, as_ = hs.apply(st_s, dels, "seq")
        np.testing.assert_array_equal(av, as_)
        np.testing.assert_array_equal(np.asarray(st_v.free_stack),
                                      np.asarray(st_s.free_stack))
        np.testing.assert_array_equal(np.asarray(st_v.free_top),
                                      np.asarray(st_s.free_top))

    @pytest.mark.parametrize("seed", range(4))
    def test_random_valid_chains(self, seed):
        """Randomized protocol-valid record streams (same-key records
        alternate insert/delete, as the lock FIFO guarantees)."""
        rng = np.random.default_rng(200 + seed)
        live = {}
        entries = []
        slot_ctr = 0
        for _ in range(12):
            key = int(rng.integers(1, 7))
            if live.get(key):
                entries.append((2, key) + live[key])
                live[key] = None
            else:
                loc = (int(rng.integers(0, P)), slot_ctr % 16, slot_ctr + 1)
                slot_ctr += 1
                entries.append((1, key) + loc)
                live[key] = loc
        self._check(_recs(*entries), C=16)


class TestBatchedGets:
    def test_get_batch_matches_individual_gets(self):
        st = kv.init_state()
        rounds = [[(INSERT, k, v(k)) for k in (1, 2, 3, 4)],
                  [(INSERT, k, v(k)) for k in (5, 6, 1, 2)]]  # 1,2 fail
        for ops in rounds:
            op = jnp.asarray([o[0] for o in ops], jnp.int32)
            key = jnp.asarray([o[1] for o in ops], jnp.uint32)
            val = jnp.asarray([o[2] for o in ops], jnp.int32)
            st, _ = step(st, op, key, val)

        @jax.jit
        def batch_get(st, keys):
            _st, v, f = mgr.runtime.run(
                lambda s, k: kv.get_batch(s, k), st, keys)
            return v, f

        keys = jnp.asarray([[1, 2, 3, 9], [5, 6, 9, 1],
                            [4, 4, 4, 4], [9, 9, 9, 9]], jnp.uint32)
        values, found = batch_get(st, keys)
        values, found = np.asarray(values), np.asarray(found)
        expect_found = np.array([[1, 1, 1, 0], [1, 1, 0, 1],
                                 [1, 1, 1, 1], [0, 0, 0, 0]], bool)
        np.testing.assert_array_equal(found, expect_found)
        np.testing.assert_array_equal(values[0, 0], v(1))
        np.testing.assert_array_equal(values[2, 3], v(4))


# ------------------------------------------------------- read tier (§8)
cmgr = make_manager(P)
ckv = KVStore(None, "kv_cached", cmgr, slots_per_node=S, value_width=W,
              num_locks=LOCKS, index_capacity=64, cache_slots=64)


@jax.jit
def cached_window_step(st, op, key, val):
    return cmgr.runtime.run(ckv.op_window, st, op, key, val)


@jax.jit
def cached_get_batch(st, keys, preds):
    return cmgr.runtime.run(
        lambda s, k, p: ckv.get_batch(s, k, pred=p), st, keys, preds)


@jax.jit
def cached_vs_reference_reads(st, keys):
    """Both read paths on the SAME state: the cached tier and the retained
    uncached specification.  Returns ((values, found) cached,
    (values, found) reference)."""
    def prog(s, k):
        pred = jnp.ones(k.shape, jnp.bool_)
        cv, cf, _ct, _cache = ckv._get_window(s, k, pred)
        rv, rf, _rt = ckv._get_window_reference(s, k, pred)
        return (cv, cf), (rv, rf)
    return cmgr.runtime.run(prog, st, keys)


def _drive_cached(windows):
    st = ckv.init_state()
    outs = []
    for w in windows:
        op = jnp.asarray([[o[0] for o in lane] for lane in w], jnp.int32)
        key = jnp.asarray([[o[1] for o in lane] for lane in w], jnp.uint32)
        val = jnp.asarray([[o[2] for o in lane] for lane in w], jnp.int32)
        st, res = cached_window_step(st, op, key, val)
        outs.append(jax.tree.map(np.asarray, res))
    return st, outs


class TestReadTier:
    """The locality-managed read tier (DESIGN.md §8): counter-validated
    cache + coalesced verb, pinned against the uncached specification and
    checked for coherence under every mutation pattern."""

    def test_cached_store_windows_match_oracle(self):
        """The full oracle suite runs against a cache-enabled store: the
        tier must be observably invisible."""
        check_windows_against_oracle([
            [[(INSERT, 1, v(1)), (INSERT, 2, v(2))],
             [(INSERT, 3, v(3)), (INSERT, 4, v(4))],
             [NOPR, NOPR], [NOPR, NOPR]],
            [[(GET, 4, v(0)), (GET, 3, v(0))],
             [(GET, 2, v(0)), (GET, 9, v(0))],
             [(GET, 1, v(0)), NOPR], [NOPR, (GET, 2, v(0))]],
            # the same reads again: served from the cache, same answers
            [[(GET, 4, v(0)), (GET, 3, v(0))],
             [(GET, 2, v(0)), (GET, 9, v(0))],
             [(GET, 1, v(0)), NOPR], [NOPR, (GET, 2, v(0))]],
            # mutate under the cached rows, then re-read
            [[(UPDATE, 4, v(4, 7)), (DELETE, 3, v(0))],
             [NOPR, NOPR], [NOPR, NOPR], [NOPR, NOPR]],
            [[(GET, 4, v(0)), (GET, 3, v(0))],
             [(GET, 4, v(0)), (GET, 3, v(0))],
             [(GET, 4, v(0)), NOPR], [NOPR, (GET, 4, v(0))]],
        ], store_mgr=cmgr, store=ckv)

    @pytest.mark.parametrize("seed", range(4))
    def test_random_cached_windows_match_oracle(self, seed):
        rng = np.random.default_rng(300 + seed)
        keys = list(range(1, 7))
        B = 3
        windows = []
        for rnd in range(5):
            w = []
            for p in range(P):
                lane = []
                for b in range(B):
                    op = int(rng.choice(
                        [NOP, GET, INSERT, UPDATE, DELETE],
                        p=[.1, .35, .25, .15, .15]))
                    key = int(rng.choice(keys))
                    lane.append((op, key, v(key, rnd * B + b)))
                w.append(lane)
            windows.append(w)
        check_windows_against_oracle(windows, store_mgr=cmgr, store=ckv)

    @pytest.mark.parametrize("seed", range(4))
    def test_cached_reads_pinned_bitwise_to_reference_under_mutation(
            self, seed):
        """Acceptance: after EVERY window of a randomized interleaved
        mutation history, the cached ``_get_window`` and the uncached
        ``_get_window_reference`` return bit-identical (values, found) on
        the same state — the cache never serves anything the wire would
        not."""
        rng = np.random.default_rng(400 + seed)
        keys = list(range(1, 7))
        probe = jnp.broadcast_to(
            jnp.arange(1, 9, dtype=jnp.uint32), (P, 8))
        st = ckv.init_state()
        for rnd in range(6):
            op = rng.choice([NOP, GET, INSERT, UPDATE, DELETE],
                            size=(P, 2), p=[.1, .3, .25, .2, .15])
            kk = rng.choice(keys, size=(P, 2))
            vv = np.stack([kk * 11 + rnd, kk * 13 + rnd],
                          axis=-1).astype(np.int32)
            st, _res = cached_window_step(
                st, jnp.asarray(op, jnp.int32), jnp.asarray(kk, jnp.uint32),
                jnp.asarray(vv))
            (cv, cf), (rv, rf) = cached_vs_reference_reads(st, probe)
            np.testing.assert_array_equal(np.asarray(cf), np.asarray(rf))
            np.testing.assert_array_equal(np.asarray(cv), np.asarray(rv))

    def test_update_invalidates_cached_row(self):
        # participant 0 inserts key 5; everyone caches it; participant 2
        # updates it; every cached copy must be dropped (same slot ctr!)
        w_ins = [[(INSERT, 5, v(5))]] + [[NOPR]] * (P - 1)
        w_get = [[(GET, 5, v(0))] for _ in range(P)]
        w_upd = [[NOPR], [NOPR], [(UPDATE, 5, (42, 43))], [NOPR]]
        _st, outs = _drive_cached([w_ins, w_get, w_get, w_upd, w_get])
        for p in range(P):
            np.testing.assert_array_equal(outs[1].value[p][0], v(5))
            np.testing.assert_array_equal(outs[2].value[p][0], v(5))
            np.testing.assert_array_equal(outs[4].value[p][0], (42, 43))

    def test_delete_invalidates_cached_row(self):
        w_ins = [[(INSERT, 5, v(5))]] + [[NOPR]] * (P - 1)
        w_get = [[(GET, 5, v(0))] for _ in range(P)]
        w_del = [[NOPR], [(DELETE, 5, v(0))], [NOPR], [NOPR]]
        _st, outs = _drive_cached([w_ins, w_get, w_del, w_get])
        assert all(bool(outs[1].found[p][0]) for p in range(P))
        assert not any(bool(outs[3].found[p][0]) for p in range(P))

    def test_slot_reuse_bumps_counter_past_cache(self):
        # delete key 5 and re-insert key 7 into the SAME slot: a stale
        # cached row for (node, slot) fails counter validation for 7 and
        # the index lookup already fails for 5.
        w_ins = [[(INSERT, 5, v(5))]] + [[NOPR]] * (P - 1)
        w_get5 = [[(GET, 5, v(0))] for _ in range(P)]
        w_cycle = [[(DELETE, 5, v(0)), (INSERT, 7, v(7))]] + \
            [[NOPR, NOPR]] * (P - 1)
        w_get = [[(GET, 5, v(0)), (GET, 7, v(0))] for _ in range(P)]
        st, outs = _drive_cached([w_ins, w_get5, w_cycle, w_get])
        for p in range(P):
            assert not bool(outs[3].found[p][0])
            assert bool(outs[3].found[p][1])
            np.testing.assert_array_equal(outs[3].value[p][1], v(7))

    def test_warm_reads_cost_zero_wire_bytes_and_count_hits(self):
        st = ckv.init_state()
        w_ins = [[(INSERT, 1 + p, v(1 + p))] for p in range(P)]
        op = jnp.asarray([[o[0] for o in lane] for lane in w_ins], jnp.int32)
        kk = jnp.asarray([[o[1] for o in lane] for lane in w_ins], jnp.uint32)
        vv = jnp.asarray([[o[2] for o in lane] for lane in w_ins], jnp.int32)
        st, _res = cached_window_step(st, op, kk, vv)
        keys = jnp.broadcast_to(jnp.arange(1, 1 + P, dtype=jnp.uint32),
                                (P, P))
        preds = jnp.ones((P, P), jnp.bool_)
        cmgr.traffic.enable().reset()
        fresh = jax.jit(lambda s, k, p: cmgr.runtime.run(
            lambda ss, kk, pp: ckv.get_batch(ss, kk, pred=pp), s, k, p))
        st, _v, f = fresh(st, keys, preds)
        jax.block_until_ready(f)
        assert bool(jnp.all(f))
        cold = cmgr.traffic.total_bytes()
        cmgr.traffic.reset()
        st, _v, f = fresh(st, keys, preds)
        jax.block_until_ready(f)
        warm = cmgr.traffic.total_bytes()
        cs = cmgr.traffic.cache_summary()["kv_cached.readcache"]
        cmgr.traffic.disable().reset()
        assert bool(jnp.all(f))
        assert cold > 0.0
        assert warm == 0.0, "all-hit window must put nothing on the wire"
        # P participants × (P-1) remote lanes each, all hits on the warm call
        assert cs["hits"] == P * (P - 1) and cs["hit_rate"] == 1.0

    def test_get_batch_pred_masks_lanes(self):
        st = ckv.init_state()
        op = jnp.asarray([[INSERT]] * P, jnp.int32)
        kk = jnp.asarray([[1 + p] for p in range(P)], jnp.uint32)
        vv = jnp.asarray([[v(1 + p)] for p in range(P)], jnp.int32)
        st, _res = cached_window_step(st, op, kk, vv)
        keys = jnp.broadcast_to(jnp.arange(1, 5, dtype=jnp.uint32), (P, 4))
        preds = jnp.asarray(np.tile([True, False, True, False], (P, 1)))
        st, vals, found = cached_get_batch(st, keys, preds)
        found, vals = np.asarray(found), np.asarray(vals)
        assert found[:, 0].all() and found[:, 2].all()
        assert not found[:, 1].any() and not found[:, 3].any()
        np.testing.assert_array_equal(vals[:, 1], np.zeros((P, W)))
        for p in range(P):
            np.testing.assert_array_equal(vals[p, 0], v(1))
            np.testing.assert_array_equal(vals[p, 2], v(3))


# ---------------------------- service-round row writes gated on lane kinds
_gate_kw = dict(slots_per_node=S, value_width=W, num_locks=LOCKS,
                index_capacity=64)
gmgr = make_manager(P)
GATE_STORES = {
    "locked": KVStore(None, "kv_gate", gmgr, **_gate_kw),
    "lockfree": KVStore(None, "kv_gate_lf", gmgr, lockfree=True,
                        **_gate_kw),
}
gate_ref = KVStore(None, "kv_gate_ref", gmgr, reference_impl=True,
                   **_gate_kw)


def _lanes(ops_keys, salt=0):
    """Two lanes per participant from a flat (op, key) list in
    (participant, lane) order."""
    flat = [(op, k, v(k, salt)) for op, k in ops_keys]
    return [flat[2 * p:2 * p + 2] for p in range(P)]


SEEDED = _lanes([(INSERT, k) for k in range(1, 9)])
PROBE = _lanes([(GET, k) for k in range(1, 9)])
GATE_CASES = {
    "insert_only": ("locked", [
        _lanes([(INSERT, k) for k in range(1, 9)]), PROBE]),
    "update_only": ("locked", [
        SEEDED, _lanes([(UPDATE, k) for k in range(1, 9)], salt=1), PROBE]),
    "delete_only": ("locked", [
        SEEDED, _lanes([(DELETE, k) for k in range(1, 9)]), PROBE]),
    "get_only": ("locked", [SEEDED, PROBE]),
    # one participant's one INSERT lane opens the INSERT writes for all
    "one_insert_lane": ("locked", [
        SEEDED, _lanes([(UPDATE, 1), (GET, 2), (UPDATE, 3), (GET, 4),
                        (GET, 5), (INSERT, 9), (UPDATE, 7), (GET, 8)],
                       salt=2),
        _lanes([(GET, k) for k in (1, 3, 5, 7, 9, 2, 4, 8)])]),
    # INSERT behind a DELETE of the same key: a second service round
    "insert_behind_delete": ("locked", [
        SEEDED, [[(DELETE, 3, v(3)), NOPR], [(INSERT, 3, v(3, 3)), NOPR]]
        + [[NOPR, NOPR]] * (P - 2), PROBE]),
    # a mixed window on the lock-free store takes the locked rounds
    "lockfree_fallback": ("lockfree", [
        SEEDED, _lanes([(UPDATE, 1), (UPDATE, 2), (DELETE, 3), (GET, 4),
                        (UPDATE, 5), (INSERT, 9), (GET, 7), (UPDATE, 8)],
                       salt=4),
        _lanes([(GET, k) for k in (1, 2, 3, 5, 9, 4, 7, 8)])]),
}


def _index_view(skv, st, keys=range(1, 17)):
    """What ``skv``'s own lookup reads for ``keys`` on every participant:
    found, and (node, slot, ctr) where found."""
    ks = jnp.broadcast_to(jnp.asarray(list(keys), jnp.uint32),
                          (P, len(keys)))
    found, _pos, node, slot, ctr = jax.jit(lambda s, k: gmgr.runtime.run(
        lambda ss, kk: jax.vmap(lambda q: skv._index_lookup(ss, q))(kk),
        s, k))(st, ks)
    found = np.asarray(found)
    where = np.stack([np.asarray(node), np.asarray(slot),
                      np.asarray(ctr).astype(np.int64)])
    return found, np.where(found, where, 0)


@pytest.mark.parametrize("case", sorted(GATE_CASES))
def test_gated_row_writes_match_reference(case):
    """The service round writes INSERT rows only when some lane of the
    window inserts, and UPDATE/DELETE rows only when some lane rewrites
    one: a skipped write is the identity.  Every window commits the same
    state leaves and answers as the ``reference_impl`` store (which
    writes unconditionally), and the answers match the oracle.  The index
    leaf is laid out differently by the reference's flat scan, so it is
    compared by what each store's own lookup reads from it."""
    store_name, windows = GATE_CASES[case]
    store = GATE_STORES[store_name]
    st, outs = drive_windows(windows, store_mgr=gmgr, store=store)
    st_r, outs_r = drive_windows(windows, store_mgr=gmgr, store=gate_ref)
    for la, lb in zip(jax.tree.leaves(st._replace(idx=None)),
                      jax.tree.leaves(st_r._replace(idx=None))):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    for got, want in zip(_index_view(store, st), _index_view(gate_ref, st_r)):
        np.testing.assert_array_equal(got, want)
    oracle = Oracle()
    for rnd, (w, res, res_r) in enumerate(zip(windows, outs, outs_r)):
        for la, lb in zip(jax.tree.leaves(res), jax.tree.leaves(res_r)):
            np.testing.assert_array_equal(la, lb, err_msg=f"window {rnd}")
        expect = oracle.apply_window(w)
        for p, lane in enumerate(w):
            for b, (op, key, _val) in enumerate(lane):
                if op == NOP:
                    continue
                if op == GET:
                    assert bool(res.found[p][b]) == (expect[p][b] is not None)
                    if expect[p][b] is not None:
                        np.testing.assert_array_equal(res.value[p][b],
                                                      expect[p][b])
                else:
                    assert bool(res.found[p][b]) == expect[p][b], \
                        f"window {rnd} p{p}b{b} op{op}({key})"

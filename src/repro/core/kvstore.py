"""KVStore channel — the paper's linearizable key-value store (§6, App. C).

Composition (all LOCO primitives):

* values + consistency metadata live in a :class:`SharedRegion` striped
  across participants — each row is ``[payload | counter | valid | checksum]``
  (the paper's per-slot metadata verbatim);
* every participant maintains a *local index* mapping key → (node, slot,
  counter) — an **open-addressing hash table** in device memory (the
  paper's host-side unordered_map; see DESIGN.md §7): linear probing from
  ``hash(key) % C`` over a bounded window of ``PROBE`` positions, with
  tombstones so deletion never breaks probe chains.  Lookup, insert and
  delete are O(PROBE) — work-proportional, independent of the provisioned
  capacity C.  ``_index_lookup_reference`` keeps the O(C) flat scan as the
  executable specification (bit-for-bit equal results), and
  ``reference_impl=True`` builds a store on the reference scan + sequential
  tracker apply for regression benchmarking;
* insertion/deletion/update are protected by an array of ticket locks,
  ``lock = key % NUM_LOCKS`` (:class:`TicketLockArray`);
* index updates propagate through the *tracker* — per-participant broadcast
  records applied by every node, acknowledged through an SST (the paper's
  tracker ringbuffers; in lockstep rounds each participant has at most one
  record in flight per round, so the P rings fuse into one P-record
  all-gather — same protocol, one collective);
* **lookups take no locks**: local index probe + one-sided remote read,
  validated by checksum (tearing), counter (stale index) and valid bit
  (in-flight insert/delete) — returning the value, EMPTY, or retrying,
  exactly per Fig. 3 / Appendix C.

Linearization points follow Appendix C: writes at row placement, deletes at
valid-bit unset, inserts at valid-bit set, reads per the case analysis.  The
linearizability test replays the induced total order against a sequential
oracle (tests/test_kvstore.py).

Windowed mutation rounds (the paper's §7 "large window" mode, for writes)
-------------------------------------------------------------------------

:meth:`KVStore.op_window` lets every participant submit a ``(B,)`` window of
mixed NOP/GET/INSERT/UPDATE/DELETE operations executed in **one traced
collective round-set**: one batched lock acquire (P·B ticket requests in a
single all-gather), one batched pre-window read serving every GET, then
service rounds in which each participant executes *all* the window slots
whose locks it currently holds — (P·B, 5) tracker records gathered and
applied in one sweep, multi-record SST acks, and one batched one-sided
write covering every UPDATE/DELETE of the round.

Window semantics (intra-window ordering and linearization points):

* **GETs linearize at the window start**: every GET lane performs the
  lock-free validated read of Fig. 3 against the pre-window state, Appendix
  C case analysis elementwise (same read path as :meth:`get_batch`).
* **Mutations linearize in per-lock FIFO order.**  Tickets for the whole
  window are issued in (participant, window slot) lexicographic order, so
  conflicting mutations — same key implies same lock — resolve in
  *participant-then-window* order: all of participant p's window beats
  participant p+1's for the same lock, and one participant's same-lock ops
  execute in window order.  Each mutation's linearization point is per
  Appendix C (insert at valid-bit set, delete at valid-bit unset, update at
  row placement), at the service round in which its ticket serves.
* Non-conflicting mutations from different window slots execute
  concurrently in the same service round.  Each lock queue serves its
  longest *conflict-free prefix* per round (same-key pairs and
  INSERT-behind-DELETE pairs serialize; distinct-key mutations commute and
  batch), so the number of service rounds is the maximum per-lock
  **conflict depth** — a window of P·B distinct-key mutations completes in
  one round regardless of how the lock stripe hashes them.
* An INSERT that exhausts the host's ``free_stack`` or finds no free local
  index position (``idx_overflow`` latched) reports ``found=False``; the
  un-indexed slot is returned to the free stack.

:meth:`op_round` (one op per participant) is the B=1 wrapper around
:meth:`op_window`; ``_op_round_reference`` keeps the original scalar
implementation as the executable specification the regression suite pins
``op_window`` against bit-for-bit.

The locality-managed read tier (DESIGN.md §8)
---------------------------------------------

Reads are where the paper's explicit-locality model pays off, so the GET
paths run through a two-layer tier:

* **coalescing** (``coalesce_reads=``, default on): duplicate (node, slot)
  GET lanes are deduplicated per participant before the wire — modeled
  read bytes scale with *unique* remote rows, not lane count
  (:func:`colls.remote_read_coalesced`);
* **caching** (``cache_slots=``, default off): a direct-mapped
  :class:`~repro.core.cache.ReadCache` of hot remote rows keyed by
  (node, slot), validated by the per-slot reuse counter the index already
  returns — a tag+counter hit is served from local memory at zero modeled
  wire bytes; a miss falls through to the coalesced verb and refills.
  Coherence: mutation rounds piggyback a "row mutated" flag on the tracker
  gather and every participant invalidates the touched lines; counter
  validation catches slot reuse.  An all-hit window issues zero collective
  rounds.

Both layers preserve results bit-for-bit; ``_get_window_reference`` keeps
the uncached path as the executable specification the oracle suites pin
the cached path against under interleaved mutation.

The explicit locality tier: placement + migration (DESIGN.md §10)
-----------------------------------------------------------------

The paper's channel objects "do not hide memory complexity" — placement
is the programmer's job.  Two knobs make that job expressible:

* **placement policies** (``placement=``) decide the *home node* of every
  INSERT: ``"local"`` (default — the writer hosts the row, today's
  behavior, zero protocol overhead), ``"hashed"`` (``key % P`` — load-
  balanced, reader-oblivious), ``"explicit"`` (a per-lane ``targets=``
  hint threaded through :meth:`op_window` /
  :meth:`export_window_records` — the caller homes each row on the node
  that will read it, e.g. the serving engine homing decode pages on
  their decoder).  Non-local inserts allocate at the home via a
  two-collective grant round-trip and write the row with the batched
  one-sided verb; the index protocol is unchanged (the tracker record
  simply names the home).
* **online migration**: a ``MOVE`` lane (:meth:`migrate_window`) re-homes
  a live row inside the existing windowed mutation rounds — under the
  key's ticket lock the mover reads the row at its old home, allocates a
  fresh slot at the destination, emits ONE kind-3 tracker record that
  every participant applies as tombstone+reinsert *in the same conflict
  wave* (`_apply_tracker_vectorized`), writes the row at the destination
  after all peers acknowledged, clears the vacated row, and the old home
  bumps the slot-reuse counter so stale cache lines and in-flight reads
  self-invalidate.  Moves ride the replication log like any mutation
  (the record export carries the target lane), so followers converge
  bitwise across migrations.

Placement evidence comes from the :class:`~repro.core.hottracker.HotTracker`
channel (``track_heat=True``): decayed per-(node, slot) read counters fed
by the GET paths.  :meth:`rebalance` turns them into policy — rows whose
dominant reader is remote become MOVE proposals, executed as one
migration window.  ``_migrate_reference`` (the B=1 sequential spec) and
the oracle/hypothesis suites pin migrated stores result-for-result
against never-migrated ones under interleaved GET/UPDATE/DELETE.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from . import colls
from .ack import AckKey, join
from .backends import get_backend
from .cache import ReadCache, ReadCacheState, hash_u32
from .channel import Channel
from .hottracker import HotTracker, HotTrackerState
from .lock import TicketLockArray, TicketLockArrayState, window_fifo_ranks
from .ownedvar import checksum
from .region import SharedRegion, SharedRegionState
from .runtime import Manager
from .sst import SST, SSTState

# op codes (MOVE re-homes a live row — the §10 migration lane)
NOP, GET, INSERT, UPDATE, DELETE, MOVE = 0, 1, 2, 3, 4, 5

# Test hook for the linearizability harness's seeded mutation test
# (tests/linearizability): when flipped, the lock-free window plan elects
# the FIRST same-key UPDATE as the write winner instead of the last —
# a deliberately broken commutativity rule that violates per-participant
# program order (lane b+1's update must beat lane b's).  Traces built
# while the flag is set bake the broken rule in; production code never
# reads it after trace time.
_MUTATE_FASTPATH_WINNER = False

# placement policies (DESIGN.md §10.1): who hosts an INSERTed row
PLACEMENTS = ("local", "hashed", "explicit")

# local-index slot states (DESIGN.md §7): tombstones keep probe chains
# intact across deletions; inserts reclaim them.  The index is ONE (C, 5)
# int32 row table [state | key_bits | node | slot | ctr_bits] so a probe is
# a single row gather and a tracker wave commits in a single row scatter —
# XLA-CPU gather/scatter cost is per-row, so fusing the five logical arrays
# into rows is a ~5× cut on the index hot paths.
_EMPTY, _USED, _TOMB = 0, 1, 2
IDX_STATE, IDX_KEY, IDX_NODE, IDX_SLOT, IDX_CTR = range(5)
MAX_GET_RETRIES = 3
# default bounded probe length for the open-addressing index; an insert
# whose whole window is occupied latches ``idx_overflow`` and fails.
DEFAULT_MAX_PROBE = 32


# lowbias32 avalanche hash (uint32 → uint32), the index's bucket fn —
# shared with the read cache's line placement (cache.py).
_hash_u32 = hash_u32


class KVResult(NamedTuple):
    value: jax.Array    # (W,) / (B, W) int32 payload (zeros when not found)
    found: jax.Array    # () / (B,) bool — GET: key present; mods: op succeeded
    retries: jax.Array  # () / (B,) int32 — GET checksum retries (0 clean)


class KVStoreState(NamedTuple):
    locks: TicketLockArrayState
    rows: SharedRegionState   # (S, W+3) int32: payload | ctr | valid | csum
    slot_ctr: jax.Array       # (S,) uint32 — per-slot reuse counters (host)
    free_stack: jax.Array     # (S,) int32 — host-local free slots
    free_top: jax.Array       # () int32
    idx: jax.Array            # (C, 5) int32: state|key_bits|node|slot|ctr_bits
    idx_overflow: jax.Array   # () bool — a probe window ran out of space
    acks: SSTState            # tracker ack counters
    cache: ReadCacheState     # read tier (zero-line when cache_slots == 0)
    heat: HotTrackerState     # read-heat tier (zero-row when untracked)


def _u2i(x):
    return jax.lax.bitcast_convert_type(jnp.asarray(x, jnp.uint32), jnp.int32)


def _i2u(x):
    return jax.lax.bitcast_convert_type(jnp.asarray(x, jnp.int32), jnp.uint32)


def _run_if(flag, fn, operand):
    """``fn(operand)`` when the scalar ``flag`` holds, else ``operand``
    unchanged; ``flag=None`` runs ``fn`` unconditionally.

    A 0/1-iteration ``while_loop``: the skipped step costs nothing on the
    device, and the carry keeps its buffer, so a whole-table write inside
    stays in place.  ``flag`` must be uniform across participants (e.g.
    reduced from a gather over the axis) — under vmap it then stays an
    unbatched predicate rather than a select over the carry."""
    if flag is None:
        return fn(operand)
    _f, out = jax.lax.while_loop(
        lambda c: c[0], lambda c: (jnp.zeros((), jnp.bool_), fn(c[1])),
        (flag, operand))
    return out


class KVStore(Channel):
    def __init__(self, parent, name: str, mgr: Manager, *,
                 slots_per_node: int, value_width: int = 2,
                 num_locks: int = 8, index_capacity: int | None = None,
                 index_max_probe: int | None = None,
                 cache_slots: int = 0, coalesce_reads: bool = True,
                 placement: str = "local", track_heat: bool = False,
                 heat_decay: float = 0.9, lockfree: bool = False,
                 reference_impl: bool = False, backend=None):
        super().__init__(parent, name, mgr)
        # execution protocol of the data verbs (DESIGN.md §14); defaults
        # to the manager's backend.  Threaded into the rows region so the
        # windowed read/write paths and the scalar spec agree.
        self.backend = get_backend(backend, default=mgr.backend)
        self.S = int(slots_per_node)
        self.W = int(value_width)
        self.L = int(num_locks)
        self.C = int(index_capacity or (self.S * self.P * 2))
        # bounded probe window of the hash index; a window no larger than C
        # degenerates gracefully (PROBE == C probes the whole table).
        self.PROBE = min(self.C, int(index_max_probe or DEFAULT_MAX_PROBE))
        # reference_impl=True: O(C) flat-scan index + sequential tracker
        # apply — the executable specification, kept hot-swappable so the
        # benchmark suite can measure the work-proportional paths against it.
        self.reference_impl = bool(reference_impl)
        # lockfree=True makes op_window default to the §11 lock-free
        # commuting fast path (overridable per call); it needs the
        # precomputed schedule, so the flat-scan spec store can't carry it.
        self.lockfree = bool(lockfree)
        if self.lockfree and self.reference_impl:
            raise ValueError("lockfree=True requires the scheduled "
                             "implementation (reference_impl=False)")
        # read tier (DESIGN.md §8): coalesce_reads dedupes duplicate
        # (node, slot) GET lanes before the wire; cache_slots > 0 adds a
        # direct-mapped counter-validated cache of hot remote rows in front
        # of the coalesced verb.  Both knobs preserve results bit-for-bit
        # (the uncached path survives as _get_window_reference).
        self.coalesce_reads = bool(coalesce_reads)
        self.cache = ReadCache(self, "readcache", mgr, lines=cache_slots,
                               row_width=self.W + 3, backing_slots=self.S,
                               backend=self.backend) if cache_slots else None
        # explicit locality tier (DESIGN.md §10): placement picks the home
        # node of every INSERT; track_heat feeds the HotTracker channel
        # from the GET paths so rebalance() can propose MOVEs for rows
        # whose dominant reader is remote.
        if placement not in PLACEMENTS:
            raise ValueError(f"placement must be one of {PLACEMENTS}, "
                             f"got {placement!r}")
        self.placement = placement
        self.hot = HotTracker(self, "heat", mgr, nodes=self.P, slots=self.S,
                              decay=heat_decay) if track_heat else None
        self.locks = TicketLockArray(self, "locks", mgr, num_locks=self.L)
        self.rows_region = SharedRegion(self, "data", mgr, slots=self.S,
                                        item_shape=(self.W + 3,),
                                        dtype=jnp.int32,
                                        backend=self.backend)
        self.acks = SST(self, "tracker_acks", mgr, shape=(), dtype=jnp.uint32)
        # the local index is private memory, not a network region, but we
        # account for it in the ledger like the paper's process heap.
        self.declare_region("index", (self.C, 5), jnp.int32)

    # -- row encoding ------------------------------------------------------------
    def encode_row(self, payload, ctr, valid):
        body = jnp.concatenate([
            jnp.asarray(payload, jnp.int32).reshape(self.W),
            _u2i(ctr).reshape(1),
            jnp.asarray(valid, jnp.int32).reshape(1)])
        return jnp.concatenate([body, _u2i(checksum(body)).reshape(1)])

    def decode_row(self, row):
        payload = row[:self.W]
        ctr = _i2u(row[self.W])
        valid = row[self.W + 1] != 0
        csum_ok = checksum(row[:self.W + 2]) == _i2u(row[self.W + 2])
        return payload, ctr, valid, csum_ok

    # -- state ----------------------------------------------------------------
    def init_state(self) -> KVStoreState:
        P = self.P
        return KVStoreState(
            locks=self.locks.init_state(),
            rows=self.rows_region.init_state(),
            slot_ctr=jnp.zeros((P, self.S), jnp.uint32),
            free_stack=jnp.broadcast_to(jnp.arange(self.S, dtype=jnp.int32),
                                        (P, self.S)),
            free_top=jnp.full((P,), self.S, jnp.int32),
            idx=jnp.zeros((P, self.C, 5), jnp.int32),
            idx_overflow=jnp.zeros((P,), jnp.bool_),
            acks=self.acks.init_state(),
            cache=(self.cache.init_state() if self.cache is not None
                   else ReadCache.empty_state(P, self.W + 3)),
            heat=(self.hot.init_state() if self.hot is not None
                  else HotTracker.empty_state(P)))

    # -- local index (open-addressing hash table, DESIGN.md §7) ------------------
    def _probe_window(self, key):
        """Loop-invariant probe positions for ``key``: the PROBE-length
        linear window starting at ``hash(key) % C`` (wrapping)."""
        key = jnp.asarray(key, jnp.uint32)
        h = (_hash_u32(key) % jnp.uint32(self.C)).astype(jnp.int32)
        return (h + jnp.arange(self.PROBE, dtype=jnp.int32)) % self.C

    def _probe(self, idx, key):
        """One bounded linear-probe pass for ``key`` over the (C, 5) index.

        Returns ``(has_match, match_pos, entry)`` over the PROBE-position
        window starting at ``hash(key) % C``, where ``entry`` is the
        (5,) index entry at ``match_pos``, read from the gathered window.
        A *match* is a USED position holding ``key`` with no EMPTY
        position before it in the window (an EMPTY terminates the chain —
        tombstones do not, so deletion never hides a later entry).

        O(PROBE) work in ONE row gather: the matched entry comes out of
        the window already read, never out of the whole index again.
        """
        key = jnp.asarray(key, jnp.uint32)
        pos_w = self._probe_window(key)
        w = idx[pos_w]                                 # (PROBE, 5) row gather
        states = w[:, IDX_STATE]
        emp = (states == _EMPTY).astype(jnp.int32)
        before_empty = (jnp.cumsum(emp) - emp) == 0   # strictly before 1st EMPTY
        match = before_empty & (states == _USED) & (w[:, IDX_KEY] == _u2i(key))
        first = jnp.argmax(match)
        return jnp.any(match), pos_w[first], w[first]

    def _index_lookup(self, st: KVStoreState, key):
        """key → (found, pos, node, slot, ctr); dispatches to the O(PROBE)
        hash probe or, for reference-impl stores, the O(C) flat scan.  The
        two are pinned bit-for-bit by the regression suite (not-found
        lookups report pos 0 in both, matching argmax-of-all-False)."""
        if self.reference_impl:
            return self._index_lookup_reference(st, key)
        return self._index_lookup_hash(st, key)

    def _index_lookup_hash(self, st: KVStoreState, key):
        found, mpos, entry = self._probe(st.idx, key)
        pos = jnp.where(found, mpos, 0)
        # a miss reports entry 0, as the flat scan's argmax-of-all-False does
        row = jnp.where(found, entry, st.idx[0])
        return (found, pos, row[IDX_NODE], row[IDX_SLOT], _i2u(row[IDX_CTR]))

    def _index_lookup_reference(self, st: KVStoreState, key):
        """The original flat associative scan — O(C) per key, kept verbatim
        as the executable specification the hash probe is pinned against."""
        match = (st.idx[:, IDX_STATE] == _USED) \
            & (st.idx[:, IDX_KEY] == _u2i(key))
        found = jnp.any(match)
        pos = jnp.argmax(match)
        row = st.idx[pos]
        return (found, pos, row[IDX_NODE], row[IDX_SLOT], _i2u(row[IDX_CTR]))

    # -- lock-free GET (paper Fig. 3 read path) -------------------------------------
    def _get(self, st: KVStoreState, key, pred):
        """Scalar read path — part of the ``_op_round_reference`` spec.

        On a cache-enabled store the scalar GET routes through the read
        tier as a B=1 window (hits served from the cache; refills are
        dropped — this path returns no state, and the windowed entry
        points are where refills persist)."""
        if self.cache is not None:
            values, found, tries, _st = self._get_window(
                st, jnp.reshape(jnp.asarray(key, jnp.uint32), (1,)),
                jnp.reshape(jnp.asarray(pred), (1,)))
            return values[0], found[0], tries
        found_idx, _pos, node, slot, ctr = self._index_lookup(st, key)

        def read_once(_):
            # locality tier: only live GET lanes ride the wire, and a lane
            # addressing my own node is served from local memory (zero
            # modeled wire bytes in the traffic ledger).
            row = self.backend.read(st.rows.buf, node, slot, self.axis,
                                    pred=pred & found_idx,
                                    ledger=self.mgr.traffic,
                                    verb=f"{self.full_name}.get")
            payload, row_ctr, valid, csum_ok = self.decode_row(row)
            return payload, row_ctr, valid, csum_ok

        def cond(c):
            tries, _p, _rc, _v, csum_ok = c
            retrying = pred & found_idx & ~csum_ok & (tries < MAX_GET_RETRIES)
            return jax.lax.psum(retrying.astype(jnp.int32), self.axis) > 0

        def body(c):
            tries, *_ = c
            p, rc, v, ok = read_once(None)
            return tries + 1, p, rc, v, ok

        with self.mgr.no_tracking():
            p0, rc0, v0, ok0 = read_once(None)
            tries, payload, row_ctr, valid, csum_ok = jax.lax.while_loop(
                cond, body, (jnp.int32(0), p0, rc0, v0, ok0))

        # Appendix C case analysis
        ctr_match = row_ctr == ctr
        found = found_idx & csum_ok & ctr_match & valid
        value = jnp.where(found, payload, jnp.zeros((self.W,), jnp.int32))
        return value, found, tries

    def _get_window(self, st: KVStoreState, keys, pred, look=None):
        """B lock-free GETs through the read tier (DESIGN.md §8).

        keys: (B,) uint32; pred: (B,) bool masking the GET lanes.  Returns
        (values (B, W), found (B,), tries (), state) — the returned state
        carries this window's cache refills and heat observations (and
        nothing else: GETs mutate no store data); callers thread it into
        their output state (``op_window``, :meth:`get_batch`) or drop it
        (the scalar spec path).

        Dispatch: a cache-less store runs ``_get_window_reference`` (the
        retained uncached specification, bit-for-bit the PR-2 read path);
        a cache-enabled store serves counter-validated hits from local
        memory and falls through to the coalesced verb for the misses —
        results are pinned bitwise against the reference under concurrent
        mutation by the oracle suites.  A heat-tracked store additionally
        accounts the live lanes in the HotTracker (§10.3) — observation
        only, never a result change.
        """
        keys = jnp.asarray(keys, jnp.uint32)
        pred = jnp.asarray(pred)
        if look is None:
            found_idx, _pos, node, slot, ctr = jax.vmap(
                lambda k: self._index_lookup(st, k))(keys)
            look = (found_idx, node, slot, ctr)
        if self.hot is not None:
            st = st._replace(heat=self.hot.observe(
                st.heat, look[1], look[2], pred & look[0]))
        if self.cache is None:
            values, found, tries = self._get_window_reference(
                st, keys, pred, look=look)
            return values, found, tries, st
        values, found, tries, cache = self._get_window_cached(
            st, keys, pred, look=look)
        return values, found, tries, st._replace(cache=cache)

    def _get_window_reference(self, st: KVStoreState, keys, pred, look=None):
        """The uncached read path (Fig. 3 / §7): every live GET lane pays
        the one-sided read.  Kept as the executable specification the
        cached tier is pinned against — and the production path for
        cache-less stores.  Retry-on-checksum is per-batch — one extra
        round if any predicated element tore — and the Appendix C case
        analysis is applied elementwise.  ``look`` optionally passes a
        precomputed (found, node, slot, ctr) lane lookup so callers
        probing the index anyway don't pay it twice.
        """
        keys = jnp.asarray(keys, jnp.uint32)
        pred = jnp.asarray(pred)
        if look is None:
            found_idx, _pos, node, slot, ctr = jax.vmap(
                lambda k: self._index_lookup(st, k))(keys)
        else:
            found_idx, node, slot, ctr = look

        def read_all(_):
            # locality tier: dead lanes (disabled / key absent) and
            # self-targeted lanes are masked out of the wire tensors; self
            # lanes come from local memory at zero modeled wire bytes.
            rows = self.backend.read_batch(
                st.rows.buf, node.astype(jnp.int32),
                slot.astype(jnp.int32), self.axis,
                preds=pred & found_idx, ledger=self.mgr.traffic,
                verb=f"{self.full_name}.get_batch",
                coalesce=self.coalesce_reads)            # (B, W+3)
            return jax.vmap(self.decode_row)(rows)

        def cond(c):
            tries, _p, _rc, _v, csum_ok = c
            retrying = jnp.any(pred & found_idx & ~csum_ok) \
                & (tries < MAX_GET_RETRIES)
            return jax.lax.psum(retrying.astype(jnp.int32), self.axis) > 0

        def body(c):
            tries, *_ = c
            p, rc, v, ok = read_all(None)
            return tries + 1, p, rc, v, ok

        with self.mgr.no_tracking():
            p0, rc0, v0, ok0 = read_all(None)
            tries, payload, row_ctr, valid, csum_ok = jax.lax.while_loop(
                cond, body, (jnp.int32(0), p0, rc0, v0, ok0))

        found = pred & found_idx & csum_ok & (row_ctr == ctr) & valid
        values = jnp.where(found[:, None], payload,
                           jnp.zeros((keys.shape[0], self.W), jnp.int32))
        return values, found, tries

    def _get_window_cached(self, st: KVStoreState, keys, pred, look=None):
        """The cached read path (DESIGN.md §8.2).

        Hit protocol: a lane whose (node, slot) tag-matches a cache line
        AND whose cached row re-validates — checksum clean, valid bit set,
        row counter equal to the counter the local index returned — is
        served from local memory at zero modeled wire bytes.  Counter
        validation catches slot reuse (a re-inserted slot bumped its
        counter); UPDATE/DELETE staleness cannot reach a hit because
        ``op_window`` invalidates every mutated (node, slot) from the
        mutation metadata its rounds already gather (§8.3).

        Miss lanes fall through to the coalesced one-sided read and refill
        their lines with the fetched (accepted) rows.  The whole fetch —
        including the first round — lives inside the retry while_loop, so
        an all-hit window issues **zero** collective rounds: the hot
        serving pattern (decode re-reading its active pages) skips the
        wire entirely, in wall time as well as in modeled bytes.
        """
        me = colls.my_id(self.axis)
        B = keys.shape[0]
        if look is None:
            found_idx, _pos, node, slot, ctr = jax.vmap(
                lambda k: self._index_lookup(st, k))(keys)
        else:
            found_idx, node, slot, ctr = look
        node = node.astype(jnp.int32)
        slot = slot.astype(jnp.int32)
        live = pred & found_idx
        remote = live & (node != me)
        crows, tag_hit = self.cache.lookup(st.cache, node, slot)
        cpay, cctr, cvalid, cok = jax.vmap(self.decode_row)(crows)
        hit = remote & tag_hit & cok & (cctr == ctr) & cvalid
        miss = live & ~hit

        def read_all(_):
            rows = self.backend.read_batch(
                st.rows.buf, node, slot, self.axis,
                preds=miss, ledger=self.mgr.traffic,
                verb=f"{self.full_name}.get_batch",
                coalesce=self.coalesce_reads)            # (B, W+3)
            return rows

        def cond(c):
            rounds, _p, _rc, _v, csum_ok, _cache = c
            # the first fetch is round 1 of this loop: no misses anywhere
            # → zero iterations → zero collective rounds for the window
            # (and no fetch decode, no refill scatter — the all-hit fast
            # path is pure local serve).
            retrying = jnp.any(miss & ~csum_ok) \
                & (rounds < 1 + MAX_GET_RETRIES)
            return jax.lax.psum(retrying.astype(jnp.int32), self.axis) > 0

        def body(c):
            rounds, *_ = c
            cache = c[-1]
            rows = read_all(None)
            p, rc, vd, ok = jax.vmap(self.decode_row)(rows)
            # refill accepted remote rows — no negative caching, so the
            # in-flight-insert / mid-delete cases of Appendix C always
            # re-read.
            acc = miss & ok & (rc == ctr) & vd & (node != me)
            cache = self.cache.fill(cache, node, slot, rows, acc)
            return rounds + 1, p, rc, vd, ok | ~miss, cache

        with self.mgr.no_tracking():
            rounds, payload, row_ctr, valid, csum_ok, cache = \
                jax.lax.while_loop(cond, body, (
                    jnp.int32(0), jnp.zeros((B, self.W), jnp.int32),
                    jnp.zeros((B,), jnp.uint32), jnp.zeros((B,), jnp.bool_),
                    ~miss, st.cache))

        found_miss = miss & csum_ok & (row_ctr == ctr) & valid
        found = hit | found_miss
        values = jnp.where(hit[:, None], cpay,
                           jnp.where(found_miss[:, None], payload,
                                     jnp.zeros((B, self.W), jnp.int32)))
        if self.mgr.traffic.enabled:
            self.mgr.traffic.record_cache(
                f"{self.full_name}.readcache",
                jnp.sum(hit.astype(jnp.float32)),
                jnp.sum(remote.astype(jnp.float32)))
        tries = jnp.maximum(rounds - 1, 0)
        return values, found, tries, cache

    # -- tracker application ----------------------------------------------------------
    def _apply_tracker(self, st: KVStoreState, recs):
        """Apply gathered tracker records (N, 5) in record order:
        rec = [kind(0/1=ins/2=del/3=move), key_bits, node, slot, ctr_bits].
        Kind-3 (MOVE, §10.2) carries the key's NEW location; the old one is
        recovered from the index entry it replaces, and the old host frees
        the vacated slot and bumps its reuse counter.

        N is P for single-op rounds and P·B for windows (participant-major,
        so record order IS participant-then-window order).  Returns
        (state, applied (N,) bool): kind-1 records miss when the local index
        has no free position in their probe window (``idx_overflow``
        latched), kind-2 when the key is already gone; the issuing op must
        then report failure.

        Dispatches to the vectorized wave scheduler (cost: one batched
        scatter per conflict wave) or, for reference-impl stores, the
        sequential per-record sweep.
        """
        if self.reference_impl:
            return self._apply_tracker_reference(st, recs)
        return self._apply_tracker_vectorized(st, recs)

    def _apply_tracker_vectorized(self, st: KVStoreState, recs):
        """Wave-scheduled tracker application: conflict-free record groups
        apply as ONE batched scatter each.

        Per wave, a record is *eligible* when no earlier record of the same
        key is still pending (per-lock FIFO: same key ⇒ same lock, so the
        integrated protocol emits at most one record per key per round and
        this blocking only bites on adversarial direct-fed histories; when
        a chain does block, every record after it waits, keeping failure
        commits FIFO-exact).  Eligible deletes hit distinct USED positions
        (distinct keys) and eligible inserts race for free positions with
        earliest-record-wins arbitration — losers retry next wave against
        the updated table, reproducing the sequential first-free choice.
        Hence every wave's winners touch **distinct** index positions and
        land in one committed-row scatter (plus one tombstone scatter for
        the wave's MOVE winners — a kind-3 record tombstones the position
        it vacates and reinserts at its first free-or-own position in the
        SAME wave, §10.2); the wave count is the conflict depth (1 for
        typical windows), not P·B, and per-record work is O(PROBE), not
        O(C).

        Failure commits respect FIFO order: a delete miss is final at
        eligibility (an earlier same-key record would have blocked it); an
        insert declares overflow only once every earlier record retired,
        since an earlier delete may still free a window position.

        XLA-CPU gather/scatter cost is per-row, so the wave loop works on
        the (C, 5) row table directly: a single row gather feeds all N
        probes and a single row scatter commits a wave; the remaining
        effects (host slot GC, the overflow latch) are applied once
        post-loop.  A dead round (no live records — UPDATE/GET-only) costs
        one loop-condition check plus two dropped scatters.
        """
        me = colls.my_id(self.axis)
        N = recs.shape[0]
        kind = recs[:, 0]
        key_b = recs[:, 1]
        key = _i2u(key_b)
        node = recs[:, 2]
        slot = recs[:, 3]
        ctr_b = recs[:, 4]
        live = kind != 0
        is_ins = kind == 1
        is_del = kind == 2
        is_mov = kind == 3
        is_put = is_ins | is_mov      # records that place a [USED|key|...] row
        order = jnp.arange(N, dtype=jnp.int32)

        def wave(carry):
            # all setup lives inside the body: a dead round (no live
            # records) costs the loop-condition check and nothing else, and
            # live rounds recompute these cheap (N,)-shaped quantities once
            # per conflict wave.
            idx_c, pending, applied, old_node, old_slot = carry
            earlier = order[None, :] < order[:, None]  # [i, j]: j precedes i
            same_key_earlier = earlier & (key[None, :] == key[:, None]) \
                & live[None, :]
            # probe windows are loop-invariant: only table contents change
            pos_w = jax.vmap(self._probe_window)(key)          # (N, PROBE)
            # committed rows: inserts AND move-reinserts place
            # [USED|key|node|slot|ctr] (the record's NEW location),
            # deletes [TOMB|0|node|slot|ctr] (a delete's node/slot/ctr ARE
            # the entry's current values — the service round read them)
            upd = jnp.stack(
                [jnp.where(is_put, _USED, _TOMB).astype(jnp.int32),
                 jnp.where(is_put, key_b, 0), node, slot, ctr_b], axis=-1)
            blocked = jnp.any(same_key_earlier & pending[None, :], axis=1)
            after_blocked = jnp.any(earlier & blocked[None, :], axis=1)
            elig = pending & ~blocked & ~after_blocked
            w = idx_c[pos_w]                                  # (N, PROBE, 5)
            states = w[..., IDX_STATE]
            emp = (states == _EMPTY).astype(jnp.int32)
            before_empty = (jnp.cumsum(emp, axis=1) - emp) == 0
            m = before_empty & (states == _USED) \
                & (w[..., IDX_KEY] == key_b[:, None])
            free = (states == _EMPTY) | (states == _TOMB)
            mpos = jnp.take_along_axis(
                pos_w, jnp.argmax(m, axis=1)[:, None], axis=1)[:, 0]
            fpos = jnp.take_along_axis(
                pos_w, jnp.argmax(free, axis=1)[:, None], axis=1)[:, 0]
            # a MOVE reinserts at the first free-or-own position: the
            # entry it tombstones is inside its own probe window, so a
            # found key ALWAYS has a landing position — kind-3 can miss
            # (key gone) but never overflow (§10.2).
            fpos_m = jnp.take_along_axis(
                pos_w, jnp.argmax(free | m, axis=1)[:, None], axis=1)[:, 0]
            tgt = jnp.where(is_ins, fpos, jnp.where(is_mov, fpos_m, mpos))
            valid_tgt = jnp.where(is_ins, jnp.any(free, axis=1),
                                  jnp.any(m, axis=1))
            cand = elig & valid_tgt
            # placement position races: earliest candidate wins, losers
            # retry (a mover's own matched position stays USED until it
            # wins, so only the mover itself can ever land there)
            race = earlier & (tgt[None, :] == tgt[:, None]) \
                & (cand & is_put)[None, :]
            lost = is_put & jnp.any(race, axis=1)
            win = cand & ~lost
            earlier_pending = jnp.any(earlier & pending[None, :], axis=1)
            fail = elig & ~valid_tgt & (is_del | is_mov | ~earlier_pending)
            # capture the vacated location of winning movers (slot GC and
            # the reuse-counter bump are post-loop host effects)
            mrow = w[order, jnp.argmax(m, axis=1)]             # (N, 5)
            mwin = win & is_mov
            old_node = jnp.where(mwin, mrow[:, IDX_NODE], old_node)
            old_slot = jnp.where(mwin, mrow[:, IDX_SLOT], old_slot)
            # winners occupy distinct positions: the movers' tombstones
            # and everyone's committed rows are TWO row scatters per wave
            # (a mover landing in place is tombstoned then overwritten —
            # scatter order makes that the reinsert, as required)
            tomb = jnp.stack(
                [jnp.full((N,), _TOMB, jnp.int32), jnp.zeros((N,), jnp.int32),
                 mrow[:, IDX_NODE], mrow[:, IDX_SLOT], mrow[:, IDX_CTR]],
                axis=-1)
            row_t = jnp.where(mwin, mpos, self.C)
            idx_c = idx_c.at[row_t].set(tomb, mode="drop")
            row = jnp.where(win, tgt, self.C)
            idx_c = idx_c.at[row].set(upd, mode="drop")
            return idx_c, pending & ~(win | fail), applied | win, \
                old_node, old_slot

        idx, _pending, applied, old_node, old_slot = jax.lax.while_loop(
            lambda c: jnp.any(c[1]), wave,
            (st.idx, live, jnp.zeros((N,), jnp.bool_),
             jnp.zeros((N,), jnp.int32), jnp.zeros((N,), jnp.int32)))

        # ---- post-loop commits (nothing below feeds back into scheduling)
        # slot GC at the hosting node (counter-based GC), in record order:
        # deletes free the record's slot, moves free the VACATED one
        host_free = applied & ((is_del & (node == me))
                               | (is_mov & (old_node == me)))
        gc_slot = jnp.where(is_mov, old_slot, slot)
        hf = host_free.astype(jnp.int32)
        hrank = jnp.cumsum(hf) - hf
        back = jnp.where(host_free,
                         jnp.clip(st.free_top + hrank, 0, self.S - 1),
                         self.S)
        # §10.2 self-invalidation: the old home bumps the vacated slot's
        # reuse counter so stale cached copies and in-flight reads fail
        # counter validation even against a not-yet-refreshed index view
        bump = jnp.where(applied & is_mov & (old_node == me), old_slot,
                         self.S)
        st = st._replace(
            idx=idx,
            idx_overflow=st.idx_overflow | jnp.any(live & is_ins & ~applied),
            free_stack=st.free_stack.at[back].set(gc_slot, mode="drop"),
            free_top=st.free_top + jnp.sum(hf),
            slot_ctr=st.slot_ctr.at[bump].add(jnp.uint32(1), mode="drop"))
        if self.hot is not None:
            # vacated rows start cold for their next tenant (§10.3) —
            # every participant sees the freeing records in the gather
            st = st._replace(heat=self.hot.forget(
                st.heat, jnp.where(is_mov, old_node, node), gc_slot,
                applied & (is_del | is_mov)))
        return st, applied

    def _apply_tracker_reference(self, st: KVStoreState, recs):
        """The original sequential sweep — the executable specification.

        Flat-index placement policy (first EMPTY position anywhere, O(C)
        argmax; deletes clear back to EMPTY — the flat scan needs no
        tombstones).  Live records are compacted to the front (stable, so
        the participant-then-window order is preserved) and applied under a
        dynamic-trip-count loop: a round with r live records costs r
        sequential applications.  Logically equivalent to the vectorized
        wave scheduler (same applied flags, same key → (node, slot, ctr)
        mapping, same free-slot accounting); index *layouts* differ by
        placement policy, which is why each impl pairs with its own lookup.
        """
        me = colls.my_id(self.axis)
        live = recs[:, 0] != 0
        liv = live.astype(jnp.int32)
        n_live = jnp.sum(liv)
        # stable partition (live first) via cumsum ranks — O(N), no sort
        pos = jnp.where(live, jnp.cumsum(liv) - liv,
                        n_live + jnp.cumsum(1 - liv) - (1 - liv))
        perm = jnp.zeros((recs.shape[0],), jnp.int32).at[pos].set(
            jnp.arange(recs.shape[0], dtype=jnp.int32))

        def apply_one(k, carry):
            st_c, applied = carry
            p = perm[k]
            kind, key_b, node, slot, ctr_b = (recs[p, 0], recs[p, 1],
                                              recs[p, 2], recs[p, 3],
                                              recs[p, 4])
            # INSERT: place at first empty index position
            free = st_c.idx[:, IDX_STATE] == _EMPTY
            has_free = jnp.any(free)
            ins_pos = jnp.argmax(free)
            do_ins = (kind == 1) & has_free
            overflow = st_c.idx_overflow | ((kind == 1) & ~has_free)
            # DELETE: clear matching entry; host frees the slot.
            # MOVE (kind-3, §10.2): re-point the matched entry IN PLACE to
            # the record's new location (the flat scan needs no tombstone
            # dance — each impl pairs its own placement with its own
            # lookup); the OLD host frees the vacated slot and bumps its
            # reuse counter, logically equivalent to the wave scheduler.
            match = (st_c.idx[:, IDX_STATE] == _USED) \
                & (st_c.idx[:, IDX_KEY] == key_b)
            del_pos = jnp.argmax(match)
            do_del = (kind == 2) & jnp.any(match)
            do_mov = (kind == 3) & jnp.any(match)
            pos = jnp.where(do_ins, ins_pos, del_pos)
            old = st_c.idx[pos]
            ins_row = jnp.stack([jnp.int32(_USED), key_b, node, slot, ctr_b])
            del_row = jnp.concatenate(
                [jnp.zeros((2,), jnp.int32), old[IDX_NODE:]])
            new_row = jnp.where(do_ins | do_mov, ins_row,
                                jnp.where(do_del, del_row, old))
            st_c = st_c._replace(
                idx=st_c.idx.at[pos].set(new_row),
                idx_overflow=overflow)
            # slot GC at the hosting node (paper: counter-based GC) — a
            # move frees the VACATED slot at the old host
            host_frees = (do_del & (node == me)) \
                | (do_mov & (old[IDX_NODE] == me))
            freed = jnp.where(do_mov, old[IDX_SLOT], slot)
            top = st_c.free_top
            bump = jnp.where(do_mov & (old[IDX_NODE] == me),
                             old[IDX_SLOT], self.S)
            st_c = st_c._replace(
                free_stack=st_c.free_stack.at[jnp.clip(top, 0, self.S - 1)]
                .set(jnp.where(host_frees, freed,
                               st_c.free_stack[jnp.clip(top, 0, self.S - 1)])),
                free_top=jnp.where(host_frees, top + 1, top),
                slot_ctr=st_c.slot_ctr.at[bump].add(jnp.uint32(1),
                                                    mode="drop"))
            if self.hot is not None:
                st_c = st_c._replace(heat=self.hot.forget(
                    st_c.heat,
                    jnp.where(do_mov, old[IDX_NODE], node).reshape(1),
                    jnp.where(do_mov, old[IDX_SLOT], slot).reshape(1),
                    jnp.reshape(do_del | do_mov, (1,))))
            applied = applied.at[p].set(do_ins | do_del | do_mov)
            return st_c, applied

        applied0 = jnp.zeros((recs.shape[0],), jnp.bool_)
        _k, (st, applied) = jax.lax.while_loop(
            lambda c: c[0] < n_live,
            lambda c: (c[0] + 1, apply_one(c[0], c[1])),
            (jnp.int32(0), (st, applied0)))
        return st, applied

    # -- one service round for lock holders ------------------------------------------
    def _service_round(self, st: KVStoreState, op, key, value, lock_id,
                       ticket, pending):
        """Scalar service round — part of the ``_op_round_reference`` spec."""
        me = colls.my_id(self.axis)
        holding = pending & self.locks.holds(st.locks, lock_id, ticket)
        found, _pos, node, slot, ctr = self._index_lookup(st, key)
        do_ins = holding & (op == INSERT) & ~found
        do_upd = holding & (op == UPDATE) & found
        do_del = holding & (op == DELETE) & found

        # ---- INSERT phase 1: allocate local slot, write row with valid=0
        can_alloc = st.free_top > 0
        do_ins = do_ins & can_alloc
        my_slot = st.free_stack[jnp.maximum(st.free_top - 1, 0)]
        free_top = jnp.where(do_ins, st.free_top - 1, st.free_top)
        new_ctr = st.slot_ctr[my_slot] + jnp.uint32(1)
        row_invalid = self.encode_row(value, new_ctr, False)
        buf = st.rows.buf
        buf = buf.at[my_slot].set(jnp.where(do_ins, row_invalid, buf[my_slot]))
        slot_ctr = st.slot_ctr.at[my_slot].set(
            jnp.where(do_ins, new_ctr, st.slot_ctr[my_slot]))
        st = st._replace(rows=st.rows._replace(buf=buf), slot_ctr=slot_ctr,
                         free_top=free_top)

        # ---- tracker broadcast (insert/delete records), applied by all
        kind = jnp.where(do_ins, jnp.int32(1),
                         jnp.where(do_del, jnp.int32(2), jnp.int32(0)))
        rec = jnp.stack([kind, _u2i(key), jnp.where(do_ins, me, node),
                         jnp.where(do_ins, my_slot, slot),
                         _u2i(jnp.where(do_ins, new_ctr, ctr))])
        if self.cache is not None:
            # read-tier coherence on the scalar spec path too (§8.3)
            rec = jnp.concatenate(
                [rec, (do_upd | do_del).astype(jnp.int32).reshape(1)])
        with jax.named_scope("kv.tracker"):
            recs = jax.lax.all_gather(rec, self.axis, axis=0)        # (P, 5|6)
            if self.cache is not None:
                st = st._replace(cache=self.cache.invalidate(
                    st.cache, recs[:, 2], recs[:, 3], recs[:, 5] != 0))
                recs = recs[:, :5]
            n_recs = jnp.sum(recs[:, 0] != 0).astype(jnp.uint32)
            st, applied = self._apply_tracker(st, recs)
        # acknowledge through the SST; inserter requires all peers caught up.
        acks, _a = self.acks.push_accumulate(st.acks, n_recs)
        my_acked = self.acks.rows(acks)[me]
        all_acked = jnp.all(self.acks.rows(acks) >= my_acked)
        st = st._replace(acks=acks)

        # ---- index overflow: an un-indexed insert fails and returns its slot
        ins_ok = do_ins & applied[me]
        fail = do_ins & ~applied[me]
        top = st.free_top
        st = st._replace(
            free_stack=st.free_stack.at[jnp.clip(top, 0, self.S - 1)]
            .set(jnp.where(fail, my_slot,
                           st.free_stack[jnp.clip(top, 0, self.S - 1)])),
            free_top=jnp.where(fail, top + 1, top))

        # ---- UPDATE: one-sided write of the full row (value, same ctr, valid)
        row_upd = self.encode_row(value, ctr, True)
        rows2, _ = self.rows_region.write(st.rows, node, slot, row_upd,
                                          pred=do_upd)
        # ---- DELETE: unset valid bit (payload cleared, ctr preserved)
        row_del = self.encode_row(jnp.zeros((self.W,), jnp.int32), ctr, False)
        rows2, _ = self.rows_region.write(rows2, node, slot, row_del,
                                          pred=do_del)
        st = st._replace(rows=rows2)

        # ---- INSERT phase 2: mark valid **after** every peer acknowledged
        row_valid = self.encode_row(value, new_ctr, True)
        # paper: inserter waits for all acks, then sets valid — order the
        # valid-bit write after the ack observation.
        gate = join(AckKey(jax.tree.leaves(acks)), ins_ok & all_acked)
        buf2 = st.rows.buf
        buf2 = buf2.at[my_slot].set(jnp.where(gate, row_valid, buf2[my_slot]))
        st = st._replace(rows=st.rows._replace(buf=buf2))

        # ---- release: critical-section effects joined before serving bump
        holding_rel = join(AckKey([st.rows.buf]), holding)
        lstate = self.locks.release(st.locks, lock_id, holding_rel)
        st = st._replace(locks=lstate)

        success = ins_ok | do_upd | do_del
        return st, pending & ~holding, holding, success

    # -- the precomputed service schedule ---------------------------------------------
    def _service_schedule(self, op, key, lock_id, ticket, want):
        """Closed-form work-proportional schedule: each lane's service
        round, computed ONCE per window from the gathered lane metadata
        (one small all-gather + (P·B)² masks, all outside the service
        loop).

        Two lane pairs on the same lock *conflict* and must serialize in
        ticket order: same-key pairs that are not both UPDATEs (the later
        op's outcome depends on the earlier one's index/validity effect),
        and INSERT behind DELETE (the insert must wait for the delete's
        slot GC so a full stack can recycle within a window).  Same-key
        UPDATE pairs commute: they leave the index untouched and the
        round's batched row write lands them last-ticket-wins, which IS the
        per-lock FIFO outcome — so a zipf-hot key no longer costs a round
        per update.

        A lane is *bad* when it conflicts with any earlier lane in its
        queue; its round is 1 + the number of bad lanes at-or-before it
        (each bad lane is a serialization barrier, and lanes never overtake
        a barrier — overtaking could steal free slots from a stalled
        earlier insert and diverge from the FIFO oracle).  Service rounds
        therefore cost the per-lock conflict depth, not the queue depth: a
        window of P·B distinct-key mutations runs in ONE round regardless
        of how the stripe hashes them.

        Returns (round_no (B,) int32 — 0 for non-mutating lanes,
        write_winner (B,) bool — False for an UPDATE whose row write is
        superseded by a later-ticket same-key UPDATE in the same round,
        any_alloc () bool — whether ANY gathered lane allocates a slot
        (INSERT/MOVE), any_write () bool — whether ANY gathered lane
        UPDATEs or DELETEs).  Both flags are uniform across participants,
        so the service rounds skip the row writes (and the placed path's
        allocation round-trip) that no lane of the window needs — the
        request is folded into this gather.
        """
        me = colls.my_id(self.axis)
        B = op.shape[0]
        lane_meta = jnp.stack(
            [lock_id.astype(jnp.int32), _u2i(ticket), _u2i(key),
             op.astype(jnp.int32), want.astype(jnp.int32)],
            axis=-1)                                           # (B, 5)
        g = jax.lax.all_gather(lane_meta, self.axis, axis=0)   # (P, B, 5)
        g = g.reshape(-1, 5)                                   # (P·B, 5)
        g_lock, g_tick, g_key, g_op, g_want = (
            g[:, 0], _i2u(g[:, 1]), g[:, 2], g[:, 3], g[:, 4] != 0)
        queued = g_want[None, :] & (g_lock[None, :] == g_lock[:, None])
        later = queued & (g_tick[None, :] > g_tick[:, None])   # [i,j]: j>i
        round_all, winner_all = self._schedule_core(g_key, g_op, g_want,
                                                    queued, later)
        any_alloc, any_write = self._window_kinds(g_op, g_want)
        return (jax.lax.dynamic_slice(round_all, (me * B,), (B,)),
                jax.lax.dynamic_slice(winner_all, (me * B,), (B,)),
                any_alloc, any_write)

    @staticmethod
    def _window_kinds(g_op, g_want):
        """(any_alloc, any_write) over the gathered lanes: whether any
        lock-wanting lane allocates a slot (INSERT/MOVE), and whether any
        rewrites a live row (UPDATE/DELETE)."""
        return (jnp.any(g_want & ((g_op == INSERT) | (g_op == MOVE))),
                jnp.any(g_want & ((g_op == UPDATE) | (g_op == DELETE))))

    @staticmethod
    def _schedule_core(g_key, g_op, g_want, queued, later):
        """The schedule arithmetic over all N = P·B gathered lanes, shared
        by the two callers that disagree only on how they know the
        per-lock service order:

        * :meth:`_service_schedule` compares the issued **tickets** —
          ``later[i, j] = queued & (ticket_j > ticket_i)``;
        * the lock-free window plan (§11) never materializes tickets and
          passes the **(participant, lane) lexicographic order** instead —
          bit-identical, because tickets on one lock are issued in exactly
          that order (:func:`repro.core.lock.window_fifo_ranks`).

        ``queued[i, j]`` must be "lane j wants lane i's lock"; ``later``
        must be a subset of ``queued``.  Returns (round_all (N,) int32 —
        0 for non-mutating lanes, winner_all (N,) bool — False for an
        UPDATE whose row write a later same-key same-round UPDATE
        supersedes).
        """
        N = g_key.shape[0]
        eye = jnp.arange(N)[None, :] == jnp.arange(N)[:, None]
        at_or_before = queued & ~later
        before = at_or_before & ~eye
        both_upd = (g_op[:, None] == UPDATE) & (g_op[None, :] == UPDATE)
        # allocating lanes (INSERT, MOVE) behind freeing lanes (DELETE,
        # MOVE) serialize so a full free stack can recycle within a window
        alloc_i = (g_op[:, None] == INSERT) | (g_op[:, None] == MOVE)
        free_j = (g_op[None, :] == DELETE) | (g_op[None, :] == MOVE)
        same_key = g_key[None, :] == g_key[:, None]
        conflict = (same_key & ~both_upd) | (alloc_i & free_j)
        bad = jnp.any(before & conflict, axis=1)
        round_all = jnp.where(
            g_want, 1 + jnp.sum((at_or_before & bad[None, :])
                                .astype(jnp.int32), axis=1), 0)
        # an UPDATE's row write is superseded when a later same-key UPDATE
        # lands in the same round (same round is implied for co-queued
        # same-key updates unless a barrier splits them — and a split
        # later round still wins, so checking the round is exact)
        same_round = round_all[None, :] == round_all[:, None]
        superseded = both_upd & same_key & same_round & later
        winner_all = ~jnp.any(superseded, axis=1)
        if _MUTATE_FASTPATH_WINNER:
            # seeded mutation (linearizability harness): FIRST-wins —
            # breaks same-participant same-key update pairs
            winner_all = ~jnp.any(both_upd & same_key & same_round & before,
                                  axis=1)
        return round_all, winner_all

    # -- the lock-free window plan (DESIGN.md §11) ------------------------------
    def _window_plan(self, ops, keys, lock_id, want_lock, look0):
        """ONE (B, 7) lane-metadata all-gather → everything ``op_window``
        needs to coordinate the window: the fused-FAA lock resolution
        (ranks + per-lock totals — bit-identical tickets to
        ``acquire_window`` without its packed gather), the service
        schedule (bit-identical rounds/winners to ``_service_schedule``
        without its gather — tickets on one lock are issued in
        (participant, lane) order, so the plan substitutes that order),
        the **fast-window classification**, and the §8.3 cache
        invalidation metadata the locked rounds would have carried on the
        tracker gather.

        Eligibility (``win_fast``): every lock-wanting lane in the
        gathered window is an UPDATE.  Those commute — they leave the
        index, free stacks and slot counters untouched, and the round's
        batched row write lands them last-(participant, lane)-wins, which
        IS the per-lock FIFO outcome — so the whole locked service round
        (tracker gather, wave apply, SST ack push) degenerates to one
        batched counter-validated row write.  A pure-GET window is the
        vacuous case: nothing wants a lock, nothing is written.  Computed
        from the gathered metadata, so every participant classifies
        identically.  Any INSERT/DELETE/MOVE lane anywhere fails the test
        and the window falls back to the locked schedule unchanged.

        Returns a dict of per-window coordination arrays (not state).
        """
        me = colls.my_id(self.axis)
        B = ops.shape[0]
        found0, node0, slot0, _ctr0 = look0
        # the §8.3 "row mutated" flag: an UPDATE lane overwrites the live
        # row its index view names — peers must drop cached copies (the
        # counter does not change on update, so validation alone cannot
        # catch it)
        inval = (ops == UPDATE) & found0
        lane_meta = jnp.stack(
            [lock_id.astype(jnp.int32), _u2i(keys), ops,
             want_lock.astype(jnp.int32), node0.astype(jnp.int32),
             slot0.astype(jnp.int32), inval.astype(jnp.int32)],
            axis=-1)                                          # (B, 7)
        g3 = jax.lax.all_gather(lane_meta, self.axis, axis=0)  # (P, B, 7)
        g = g3.reshape(-1, 7)                                  # (N, 7)
        g_lock, g_key, g_op, g_want = g[:, 0], g[:, 1], g[:, 2], g[:, 3] != 0
        rank, totals = window_fifo_ranks(g3[:, :, 0], g3[:, :, 3] != 0,
                                         lock_id, self.L, me)
        N = g.shape[0]
        pos = jnp.arange(N, dtype=jnp.int32)
        queued = g_want[None, :] & (g_lock[None, :] == g_lock[:, None])
        later = queued & (pos[None, :] > pos[:, None])
        round_all, winner_all = self._schedule_core(g_key, g_op, g_want,
                                                    queued, later)
        win_fast = ~jnp.any(g_want & (g_op != UPDATE))
        any_alloc, any_write = self._window_kinds(g_op, g_want)
        return dict(
            rank=rank, totals=totals,
            round_no=jax.lax.dynamic_slice(round_all, (me * B,), (B,)),
            write_winner=jax.lax.dynamic_slice(winner_all, (me * B,), (B,)),
            win_fast=win_fast,
            any_want=jnp.any(g_want),
            any_alloc=any_alloc, any_write=any_write,
            inv_node=g[:, 4], inv_slot=g[:, 5], inv_flag=g[:, 6] != 0)

    # -- one service round over the whole (B,) window ---------------------------------
    def _service_window(self, st: KVStoreState, op, key, value, lock_id,
                        ticket, pending, look, serve=None,
                        write_winner=None, homes=None, any_alloc=None,
                        any_write=None):
        """Vectorized :meth:`_service_round`: every window slot whose lock
        this participant currently holds executes in this round.

        ``homes`` (set by :meth:`op_window` when the store places
        non-locally or the caller passed explicit targets) switches to the
        placed service round (:meth:`_service_window_placed`) — the same
        protocol with home-node allocation and MOVE support; ``None`` runs
        the writer-local fast path below (zero extra collectives).

        Concurrently-executing mutations hold distinct locks, hence act on
        distinct keys and distinct live slots — which is what makes the
        batched allocation, the (P·B, 5) tracker sweep and the single
        batched one-sided write below race-free.

        ``look`` is the per-lane (found, node, slot, ctr) view of the local
        index.  The index only changes through tracker records, and each
        live key appears in at most one record per round, so instead of
        re-probing the (C,)-entry index every round the view is refreshed
        incrementally from the records this round applied; the refreshed
        view is returned for the next round.

        Serving is **work-proportional**: each lock queue serves its longest
        conflict-free prefix per round, not one ticket.  Mutations of
        distinct keys commute (distinct live keys mean distinct rows, and
        the tracker applies the round's records in ticket order anyway), so
        only two pair patterns serialize: same key — the later op's outcome
        depends on the earlier one — and INSERT behind DELETE, which must
        wait for the delete's slot GC so a full stack can recycle within a
        window.  The first conflicting lane stalls its whole queue suffix
        (no overtaking — ticket FIFO remains the linearization order, and
        queue jumping could steal free slots from a stalled earlier insert).
        Service rounds therefore cost the per-lock *conflict depth*, not the
        max queue depth: a window of P·B distinct-key UPDATEs completes in
        ONE round even when a stripe lock queues 30 of them.

        Each of the round's row writes is a pass over the whole table, even
        with every lane masked, so it runs only when a lane of its kind
        exists anywhere in the window: the INSERT phases on ``any_alloc``,
        the UPDATE/DELETE write on ``any_write``.  Both flags come from the
        schedule gather, so every participant decides alike, and a skipped
        write is the identity (no lane anywhere would have written a row).
        ``None`` (the ``reference_impl`` spec path) writes unconditionally.
        """
        if homes is not None:
            return self._service_window_placed(
                st, op, key, value, lock_id, ticket, pending, look, homes,
                serve=serve, write_winner=write_winner, any_alloc=any_alloc)
        me = colls.my_id(self.axis)
        B = op.shape[0]
        if serve is None:
            # PR-1 baseline serving: one ticket per lock per round
            holding = pending & self.locks.holds(st.locks, lock_id, ticket)
            upd_winner = jnp.ones((B,), jnp.bool_)
        else:
            holding = pending & serve
            upd_winner = write_winner
        found, node, slot, ctr = look
        do_ins = holding & (op == INSERT) & ~found
        do_upd = holding & (op == UPDATE) & found
        do_del = holding & (op == DELETE) & found

        # ---- INSERT phase 1: allocate local slots, write rows with valid=0.
        # Window-rank allocation: insert lane j takes the (rank_j)-th slot
        # from the top of the free stack; ranks past the stack depth fail
        # (capacity exhaustion) — failures form a suffix of the ranks, so
        # surviving ranks stay dense.
        ins = do_ins.astype(jnp.int32)
        ins_rank = jnp.cumsum(ins) - ins                      # exclusive (B,)
        do_ins = do_ins & (ins_rank < st.free_top)
        my_slot = st.free_stack[
            jnp.clip(st.free_top - 1 - ins_rank, 0, self.S - 1)]
        free_top = st.free_top - jnp.sum(do_ins.astype(jnp.int32))
        new_ctr = st.slot_ctr[my_slot] + jnp.uint32(1)

        def write_invalid(rows):
            row_invalid = jax.vmap(
                lambda v, c: self.encode_row(v, c, False))(value, new_ctr)
            return self.rows_region.local_write_batch(
                rows, my_slot, row_invalid, preds=do_ins)

        with jax.named_scope("kv.insert"):
            rows_inv = _run_if(any_alloc, write_invalid, st.rows)
        ctr_row = jnp.where(do_ins, my_slot, self.S)          # drop non-lanes
        slot_ctr = st.slot_ctr.at[ctr_row].set(new_ctr, mode="drop")
        st = st._replace(rows=rows_inv, slot_ctr=slot_ctr, free_top=free_top)

        # ---- tracker broadcast: B records per participant, one (P·B, 5) sweep
        kind = jnp.where(do_ins, jnp.int32(1),
                         jnp.where(do_del, jnp.int32(2), jnp.int32(0)))
        rec = jnp.stack([kind, _u2i(key),
                         jnp.where(do_ins, me, node).astype(jnp.int32),
                         jnp.where(do_ins, my_slot, slot).astype(jnp.int32),
                         _u2i(jnp.where(do_ins, new_ctr, ctr))],
                        axis=1)                                # (B, 5)
        if self.cache is not None:
            # read-tier coherence (DESIGN.md §8.3): piggyback a "row
            # mutated" flag on the tracker gather — an UPDATE lane's rec is
            # kind-0 but its node/slot columns already carry the row it is
            # about to write, so one extra int column is all the metadata
            # every peer needs to invalidate its cached copy.  (INSERTs
            # need no invalidation: slot reuse bumps the counter the hit
            # protocol validates.)
            rec = jnp.concatenate(
                [rec, (do_upd | do_del).astype(jnp.int32)[:, None]], axis=1)
        with jax.named_scope("kv.tracker"):
            recs = jax.lax.all_gather(rec, self.axis, axis=0)  # (P, B, 5|6)
            recs = recs.reshape(-1, rec.shape[1])          # participant-major
            if self.cache is not None:
                st = st._replace(cache=self.cache.invalidate(
                    st.cache, recs[:, 2], recs[:, 3], recs[:, 5] != 0))
                recs = recs[:, :5]
            n_recs = jnp.sum(recs[:, 0] != 0).astype(jnp.uint32)
            st, applied = self._apply_tracker(st, recs)
        my_applied = jax.lax.dynamic_slice(applied, (me * B,), (B,))
        # acknowledge all applied records through the SST in one push;
        # inserters require every peer caught up before setting valid.
        acks, _a = self.acks.push_accumulate(st.acks, n_recs)
        my_acked = self.acks.rows(acks)[me]
        all_acked = jnp.all(self.acks.rows(acks) >= my_acked)
        st = st._replace(acks=acks)

        # ---- index overflow: un-indexed inserts fail and return their slots
        ins_ok = do_ins & my_applied
        fails = do_ins & ~my_applied
        f = fails.astype(jnp.int32)
        f_rank = jnp.cumsum(f) - f
        back = jnp.where(fails,
                         jnp.clip(st.free_top + f_rank, 0, self.S - 1),
                         self.S)
        st = st._replace(
            free_stack=st.free_stack.at[back].set(my_slot, mode="drop"),
            free_top=st.free_top + jnp.sum(f))

        # ---- UPDATE / DELETE: every one-sided row write of the round in ONE
        # batched collective (update rows carry (value, same ctr, valid);
        # delete rows clear the payload and unset valid, ctr preserved).
        # Same-key UPDATEs may co-serve; the schedule precomputed which
        # lane's write survives (last ticket), so superseded lanes are
        # simply masked out and the batch stays collision-free
        # (assume_unique) — no in-loop winner mask needed.
        def write_upd_del(rows):
            row_upd = jax.vmap(
                lambda v, c: self.encode_row(v, c, True))(value, ctr)
            row_del = jax.vmap(lambda c: self.encode_row(
                jnp.zeros((self.W,), jnp.int32), c, False))(ctr)
            rows2, _ = self.rows_region.write_batch(
                rows, node, slot, jnp.where(do_upd[:, None], row_upd,
                                            row_del),
                preds=(do_upd & upd_winner) | do_del, assume_unique=True)
            return rows2

        st = st._replace(rows=_run_if(any_write, write_upd_del, st.rows))

        # ---- INSERT phase 2: mark valid **after** every peer acknowledged
        gate = join(AckKey(jax.tree.leaves(acks)), ins_ok & all_acked)

        def write_valid(rows):
            row_valid = jax.vmap(
                lambda v, c: self.encode_row(v, c, True))(value, new_ctr)
            return self.rows_region.local_write_batch(
                rows, my_slot, row_valid, preds=gate)

        with jax.named_scope("kv.insert"):
            st = st._replace(rows=_run_if(any_alloc, write_valid, st.rows))

        # ---- release every lock held this round (effects joined first).
        # The scheduled path defers the now_serving bump to the end of the
        # window (op_window): no lane reads now_serving mid-window — the
        # precomputed schedule replaced the holds() test — so one batched
        # bump by the acquire totals is observably identical and saves a
        # (P, B, L) count reduction per round.
        if serve is None:
            holding_rel = join(AckKey([st.rows.buf]), holding)
            st = st._replace(locks=self.locks.release_window(
                st.locks, lock_id, holding_rel))

        # ---- refresh the per-lane index view from this round's records
        # (each live key is in at most one record, so order is irrelevant)
        rec_key = _i2u(recs[:, 1])                              # (P·B,)
        ins_rec = applied & (recs[:, 0] == 1)
        del_rec = applied & (recs[:, 0] == 2)
        m_ins = ins_rec[None, :] & (rec_key[None, :] == key[:, None])
        hit_ins = jnp.any(m_ins, axis=1)                        # (B,)
        r_idx = jnp.argmax(m_ins, axis=1)
        hit_del = jnp.any(
            del_rec[None, :] & (rec_key[None, :] == key[:, None]), axis=1)
        look = (jnp.where(hit_ins, True, found & ~hit_del),
                jnp.where(hit_ins, recs[r_idx, 2], node),
                jnp.where(hit_ins, recs[r_idx, 3], slot),
                jnp.where(hit_ins, _i2u(recs[r_idx, 4]), ctr))

        success = ins_ok | do_upd | do_del
        return st, pending & ~holding, holding, success, look

    # -- the placed service round (explicit locality tier, DESIGN.md §10) -------
    def _service_window_placed(self, st: KVStoreState, op, key, value,
                               lock_id, ticket, pending, look, homes,
                               serve=None, write_winner=None,
                               any_alloc=None):
        """One service round under explicit placement: the generalization
        of :meth:`_service_window` in which INSERT slots are allocated at
        the lane's *home* node and MOVE lanes re-home live rows.

        Differences from the writer-local fast path:

        * **allocation** is a two-collective round-trip — one (P·B, 2)
          request gather (want, home) and one (P·B, 3) grant psum (ok,
          slot, ctr).  Each home grants its requests in global
          (participant, lane) order from its own free stack, so the
          writer-local case (home == writer for every lane) degenerates
          to exactly the fast path's slot choices;
        * **phase-1/phase-2 row writes** ride the batched one-sided write
          verb addressed at the home — a self-targeted lane is a local
          store at zero modeled wire bytes (§2.3), so writer-local lanes
          cost the fast path's bytes and land the fast path's bits (the
          replication suite pins the two paths against each other:
          followers always replay through this one);
        * **MOVE** (§10.2): under the key's ticket lock the mover reads
          the row at its old home (one clean read — the lock excludes
          writers, so no retry loop), allocates at the destination, and
          emits ONE kind-3 tracker record naming the NEW location.  Every
          participant applies it as tombstone+reinsert in the same
          conflict wave (`_apply_tracker_vectorized`), the old home frees
          the vacated slot and bumps its reuse counter (stale readers and
          cache lines self-invalidate), and after all peers acknowledged
          the mover writes the row at the destination and clears the old
          one — both lanes of the round's single batched write.  A MOVE
          whose destination IS the current home succeeds with no effects.

        All mutation kinds share one final 2B-lane ``write_batch``:
        UPDATE winners and DELETE clears (ungated), ack-gated INSERT
        valid rows and MOVE destination rows, and ack-gated MOVE
        old-slot clears — every enabled lane addresses a distinct row
        (distinct keys per round; fresh destination slots; old slots are
        freed *after* this round's allocation), so ``assume_unique``
        holds.
        """
        me = colls.my_id(self.axis)
        B = op.shape[0]
        if serve is None:
            holding = pending & self.locks.holds(st.locks, lock_id, ticket)
            upd_winner = jnp.ones((B,), jnp.bool_)
        else:
            holding = pending & serve
            upd_winner = write_winner
        found, node, slot, ctr = look
        node = node.astype(jnp.int32)
        slot = slot.astype(jnp.int32)
        do_ins = holding & (op == INSERT) & ~found
        do_upd = holding & (op == UPDATE) & found
        do_del = holding & (op == DELETE) & found
        is_move = holding & (op == MOVE) & found
        do_move = is_move & (homes != node)
        move_noop = is_move & (homes == node)

        # ---- MOVE phase 0 + allocation at the home nodes.  The MOVE
        # pre-read (the lane holds the key's ticket lock, so one validated
        # read suffices — the §10.2 protocol) and the allocation
        # round-trip — one (P·B, 2) request gather (want, home) and one
        # (P·B, 3) grant psum (ok, slot, ctr) — only matter to lanes that
        # allocate (INSERT/MOVE).  The allocation *request* is folded into
        # the schedule gather (§14): callers pass ``any_alloc``, computed
        # from the lane metadata every participant already gathered, and a
        # window with no allocating lane anywhere skips both collectives
        # via the 0-iteration while_loop — a placed UPDATE/DELETE window
        # keeps the writer-local fast path's round shape.  The skipped
        # carry is the identity: no grants, no slot-counter or free-stack
        # movement, all-False aok (and the gated ledger callback never
        # fires, so reclaimed rounds are observable).  ``any_alloc=None``
        # (the scalar spec path) keeps the unconditional round-trip.
        alloc_want = do_ins | do_move

        def _alloc_body(slot_ctr, free_top):
            moved = self.backend.read_batch(
                st.rows.buf, node, slot, self.axis, preds=do_move,
                ledger=self.mgr.traffic,
                verb=f"{self.full_name}.move_read",
                coalesce=False)[:, :self.W]
            req = jnp.stack([alloc_want.astype(jnp.int32), homes], axis=-1)
            reqs = jax.lax.all_gather(req, self.axis, axis=0).reshape(-1, 2)
            g_want = reqs[:, 0] != 0
            mine = g_want & (reqs[:, 1] == me)
            mn = mine.astype(jnp.int32)
            rank = jnp.cumsum(mn) - mn
            grant = mine & (rank < free_top)
            a_slot = st.free_stack[
                jnp.clip(free_top - 1 - rank, 0, self.S - 1)]
            a_ctr = slot_ctr[a_slot] + jnp.uint32(1)
            ctr_row = jnp.where(grant, a_slot, self.S)
            slot_ctr = slot_ctr.at[ctr_row].set(a_ctr, mode="drop")
            free_top = free_top - jnp.sum(grant.astype(jnp.int32))
            tbl = jnp.where(
                grant[:, None],
                jnp.stack([jnp.ones_like(a_slot), a_slot, _u2i(a_ctr)],
                          axis=-1),
                jnp.zeros((reqs.shape[0], 3), jnp.int32))
            tbl = jax.lax.psum(tbl, self.axis)
            my_tbl = jax.lax.dynamic_slice(tbl, (me * B, 0), (B, 3))
            colls.record_rounds(
                self.mgr.traffic, f"{self.full_name}.alloc",
                self.backend.alloc_rounds, self.axis)
            return (moved, slot_ctr, free_top, grant, a_slot,
                    my_tbl[:, 0] != 0, my_tbl[:, 1], _i2u(my_tbl[:, 2]))

        if any_alloc is None:
            (moved, slot_ctr, free_top, grant, a_slot, aok, my_slot,
             new_ctr) = _alloc_body(st.slot_ctr, st.free_top)
        else:
            N = self.P * B

            def abody(c):
                return (jnp.zeros((), jnp.bool_),) + _alloc_body(c[2], c[3])

            (_t, moved, slot_ctr, free_top, grant, a_slot, aok, my_slot,
             new_ctr) = jax.lax.while_loop(
                lambda c: c[0], abody,
                (any_alloc, jnp.zeros((B, self.W), jnp.int32),
                 st.slot_ctr, st.free_top,
                 jnp.zeros((N,), jnp.bool_), jnp.zeros((N,), jnp.int32),
                 jnp.zeros((B,), jnp.bool_), jnp.zeros((B,), jnp.int32),
                 jnp.zeros((B,), jnp.uint32)))
        st = st._replace(slot_ctr=slot_ctr, free_top=free_top)
        do_ins = do_ins & aok
        do_move = do_move & aok
        placed = do_ins | do_move

        # ---- INSERT phase 1: the writer one-sided-writes the invalid row
        # at its home (a self lane is a local store, zero wire bytes)
        row_invalid = jax.vmap(
            lambda v, c: self.encode_row(v, c, False))(value, new_ctr)
        rows_inv, _ = self.rows_region.write_batch(
            st.rows, homes, my_slot, row_invalid, preds=do_ins,
            assume_unique=True)
        st = st._replace(rows=rows_inv)

        # ---- tracker broadcast: ONE record per lane — kind-1/3 records
        # name the NEW location (a kind-3's old one is recovered from the
        # index at apply time), kind-2 the current one.
        kind = jnp.where(do_ins, jnp.int32(1),
                         jnp.where(do_del, jnp.int32(2),
                                   jnp.where(do_move, jnp.int32(3),
                                             jnp.int32(0))))
        rec = jnp.stack([kind, _u2i(key),
                         jnp.where(placed, homes, node),
                         jnp.where(placed, my_slot, slot),
                         _u2i(jnp.where(placed, new_ctr, ctr))], axis=1)
        if self.cache is not None:
            # read-tier coherence (§8.3): invalidate the PRE-mutation
            # location.  For UPDATE/DELETE that is the record's own
            # (node, slot); a MOVE vacates its OLD home, which the record
            # no longer carries — so the flag column travels with the
            # old coordinates from the lane's index view.
            rec = jnp.concatenate(
                [rec,
                 (do_upd | do_del | do_move).astype(jnp.int32)[:, None],
                 node[:, None], slot[:, None]], axis=1)
        with jax.named_scope("kv.tracker"):
            recs = jax.lax.all_gather(rec, self.axis, axis=0)
            recs = recs.reshape(-1, rec.shape[1])           # participant-major
            if self.cache is not None:
                st = st._replace(cache=self.cache.invalidate(
                    st.cache, recs[:, 6], recs[:, 7], recs[:, 5] != 0))
                recs = recs[:, :5]
            n_recs = jnp.sum(recs[:, 0] != 0).astype(jnp.uint32)
            st, applied = self._apply_tracker(st, recs)
        my_applied = jax.lax.dynamic_slice(applied, (me * B,), (B,))
        acks, _a = self.acks.push_accumulate(st.acks, n_recs)
        my_acked = self.acks.rows(acks)[me]
        all_acked = jnp.all(self.acks.rows(acks) >= my_acked)
        st = st._replace(acks=acks)

        # ---- failed placements return their slots to the HOME stacks
        # (the grant table is global, so each home sees its own failures)
        fail = grant & ~applied
        fl = fail.astype(jnp.int32)
        f_rank = jnp.cumsum(fl) - fl
        back = jnp.where(fail,
                         jnp.clip(st.free_top + f_rank, 0, self.S - 1),
                         self.S)
        st = st._replace(
            free_stack=st.free_stack.at[back].set(a_slot, mode="drop"),
            free_top=st.free_top + jnp.sum(fl))
        ins_ok = do_ins & my_applied
        move_ok = do_move & my_applied

        # ---- the round's one-sided row writes, ONE 2B-lane collective
        row_upd = jax.vmap(
            lambda v, c: self.encode_row(v, c, True))(value, ctr)
        row_del = jax.vmap(lambda c: self.encode_row(
            jnp.zeros((self.W,), jnp.int32), c, False))(ctr)
        row_ins = jax.vmap(
            lambda v, c: self.encode_row(v, c, True))(value, new_ctr)
        row_mov = jax.vmap(
            lambda v, c: self.encode_row(v, c, True))(moved, new_ctr)
        gate = join(AckKey(jax.tree.leaves(acks)),
                    (ins_ok | move_ok) & all_acked)
        prim = jnp.where(do_upd[:, None], row_upd,
                         jnp.where(do_del[:, None], row_del,
                                   jnp.where(do_ins[:, None], row_ins,
                                             row_mov)))
        rows2, _ = self.rows_region.write_batch(
            st.rows,
            jnp.concatenate([jnp.where(placed, homes, node), node]),
            jnp.concatenate([jnp.where(placed, my_slot, slot), slot]),
            jnp.concatenate([prim, row_del], axis=0),
            preds=jnp.concatenate([(do_upd & upd_winner) | do_del | gate,
                                   gate & do_move]),
            assume_unique=True)
        st = st._replace(rows=rows2)

        if serve is None:
            holding_rel = join(AckKey([st.rows.buf]), holding)
            st = st._replace(locks=self.locks.release_window(
                st.locks, lock_id, holding_rel))

        # ---- refresh the per-lane index view: kind-1 AND kind-3 records
        # re-point a key; kind-2 records clear it
        rec_key = _i2u(recs[:, 1])
        put_rec = applied & ((recs[:, 0] == 1) | (recs[:, 0] == 3))
        del_rec = applied & (recs[:, 0] == 2)
        m_put = put_rec[None, :] & (rec_key[None, :] == key[:, None])
        hit_put = jnp.any(m_put, axis=1)
        r_idx = jnp.argmax(m_put, axis=1)
        hit_del = jnp.any(
            del_rec[None, :] & (rec_key[None, :] == key[:, None]), axis=1)
        look = (jnp.where(hit_put, True, found & ~hit_del),
                jnp.where(hit_put, recs[r_idx, 2], node),
                jnp.where(hit_put, recs[r_idx, 3], slot),
                jnp.where(hit_put, _i2u(recs[r_idx, 4]), ctr))

        success = ins_ok | do_upd | do_del | move_ok | move_noop
        return st, pending & ~holding, holding, success, look

    # -- public windowed round-set API ------------------------------------------------
    def _lane_homes(self, ops, keys, targets):
        """Per-lane home nodes ((B,) int32) under the store's placement
        policy, or ``None`` for the writer-local fast path (placement
        ``"local"`` with no explicit targets — today's zero-overhead
        protocol, traced without the allocation round-trip).  MOVE lanes
        home at their explicit target when one is given, else at the
        policy home (so ``"hashed"`` stores can MOVE keys back to their
        hash home without a hint)."""
        if targets is None and self.placement == "local":
            return None
        B = ops.shape[0]
        t = None
        if targets is not None:
            t = jnp.clip(jnp.asarray(targets, jnp.int32).reshape(B),
                         0, self.P - 1)
        if self.placement == "hashed":
            ph = (keys % jnp.uint32(self.P)).astype(jnp.int32)
        elif self.placement == "explicit":
            if t is None:
                raise ValueError(
                    "placement='explicit' stores need per-lane targets=")
            ph = t
        else:
            ph = jnp.broadcast_to(colls.my_id(self.axis), (B,))
        if t is None:
            return ph
        return jnp.where(ops == MOVE, t, ph)

    def op_window(self, st: KVStoreState, ops, keys, values, targets=None,
                  targets_are_homes=False, lockfree=None):
        """Every participant submits a (B,) window of mixed operations; the
        whole window executes in one traced collective round-set.  Service
        rounds run until every mutation in every window completed.  Returns
        (state, KVResult) with (B,)-batched result lanes.

        ops: (B,) int32 in {NOP, GET, INSERT, UPDATE, DELETE, MOVE}
        keys: (B,) uint32 (nonzero); values: (B, W) int32.
        targets: optional (B,) int32 per-lane placement hints (§10.1) —
        the home node of INSERT lanes under ``placement="explicit"`` and
        the destination of MOVE lanes.  MOVE lanes require the placed
        path (a non-local placement or explicit ``targets``); under the
        writer-local fast path they acquire their lock and complete as
        failures (``found=False``) with no effect.
        ``targets_are_homes=True`` (the replay entry point) bypasses the
        placement policy entirely: ``targets`` ARE the per-lane homes —
        exported records carry the leader's *resolved* homes, so a
        replica converges whatever its own policy is configured as.
        ``lockfree`` (default: the store's constructor knob) traces the
        §11 lock-free commuting fast path: windows whose lock-wanting
        lanes are all UPDATEs (pure-GET included, vacuously) are
        classified at schedule-build time from ONE fused metadata gather
        and served without lock acquisition, tracker or ack collectives —
        mixed windows fall back to the locked schedule bit-for-bit.  The
        locked path (``lockfree=False``, every existing caller) remains
        the pinned executable specification; both paths commit identical
        state bits for identical windows, which the replication and
        torture suites pin leaf-by-leaf.

        See the module docstring for the intra-window ordering and
        linearization-point contract, and DESIGN.md §11 for the fast
        path's eligibility rules and counter-validation protocol.
        """
        lockfree = self.lockfree if lockfree is None else bool(lockfree)
        if lockfree and self.reference_impl:
            raise ValueError("lockfree op_window requires the scheduled "
                             "implementation (reference_impl=False)")
        ops = jnp.asarray(ops, jnp.int32)
        B = ops.shape[0]
        keys = jnp.asarray(keys, jnp.uint32).reshape(B)
        values = jnp.asarray(values, jnp.int32).reshape(B, self.W)
        if targets_are_homes:
            homes = jnp.clip(jnp.asarray(targets, jnp.int32).reshape(B),
                             0, self.P - 1)
        else:
            homes = self._lane_homes(ops, keys, targets)
        lock_id = (keys % jnp.uint32(self.L)).astype(jnp.int32)
        want_lock = (ops == INSERT) | (ops == UPDATE) | (ops == DELETE) \
            | (ops == MOVE)

        # one (B, C) index probe for the whole window; the service loop
        # keeps the per-lane view current incrementally (tracker records
        # are the only writers of the index).
        with jax.named_scope("kv.probe"):
            found0, _pos, node0, slot0, ctr0 = jax.vmap(
                lambda k: self._index_lookup(st, k))(keys)
        look0 = (found0, node0, slot0, ctr0)

        # each phase below runs under one ``kv.<phase>`` name scope, so a
        # profiler trace gives every phase its device time; the top-level
        # scopes do not nest, and ``kv.tracker`` nests in ``kv.service``
        if not lockfree:
            plan = None
            with jax.named_scope("kv.lock_acquire"):
                lstate, ticket = self.locks.acquire_window(
                    st.locks, lock_id, want_lock)
        else:
            # §11: the plan's single gather subsumes the acquire gather
            # (fused-FAA ranks/totals → bit-identical tickets + counters)
            # and the schedule gather — and classifies the window.  A
            # window with no lock-wanting lane ANYWHERE (the pure-GET
            # serving pattern) is classified by one scalar psum instead
            # and skips the gather and the O((P·B)²) schedule arithmetic
            # outright — the skipped plan's outputs are exactly the
            # defaults the carry holds (zero ranks/totals move no ticket
            # counter, nothing to invalidate, vacuously fast).
            with jax.named_scope("kv.plan"):
                any_want = jax.lax.psum(
                    jnp.any(want_lock).astype(jnp.int32), self.axis) > 0
                N = self.P * B

                def pbody(c):
                    p = self._window_plan(ops, keys, lock_id, want_lock,
                                          look0)
                    return (jnp.zeros((), jnp.bool_), p["rank"], p["totals"],
                            p["round_no"], p["write_winner"], p["win_fast"],
                            p["any_alloc"], p["any_write"],
                            p["inv_node"], p["inv_slot"], p["inv_flag"])

                (_t, rank, totals, rno, wwin, wfast, aalloc, awrite, inode,
                 islot, iflag) = jax.lax.while_loop(
                        lambda c: c[0], pbody,
                        (any_want, jnp.zeros((B,), jnp.uint32),
                         jnp.zeros((self.L,), jnp.uint32),
                         jnp.zeros((B,), jnp.int32),
                         jnp.zeros((B,), jnp.bool_),
                         jnp.ones((), jnp.bool_),
                         jnp.zeros((), jnp.bool_),
                         jnp.zeros((), jnp.bool_),
                         jnp.zeros((N,), jnp.int32),
                         jnp.zeros((N,), jnp.int32),
                         jnp.zeros((N,), jnp.bool_)))
                plan = dict(rank=rank, totals=totals, round_no=rno,
                            write_winner=wwin, win_fast=wfast,
                            any_want=any_want, any_alloc=aalloc,
                            any_write=awrite,
                            inv_node=inode, inv_slot=islot, inv_flag=iflag)
        if not lockfree:
            # every acquired ticket completes within this window, so the
            # deferred end-of-window release bumps now_serving by exactly
            # the ticket totals the acquire added (free as a diff)
            lock_totals = lstate.next_ticket - st.locks.next_ticket
            st = st._replace(locks=lstate)

        # lock-free GETs against pre-window state (linearized at window
        # start), through the read tier; refills land in the state BEFORE
        # the service loop, so this window's own mutations invalidate any
        # line they touch (§8.3 refill-then-invalidate order).  GETs never
        # read lock state, so the lock-free dispatch is free to defer its
        # counter bumps into the gated mutation half below.
        with jax.named_scope("kv.get"):
            get_val, get_found, retries, st = self._get_window(
                st, keys, ops == GET, look=look0)

        if self.reference_impl:
            round_no = write_winner = any_alloc = any_write = None
        elif not lockfree:
            # work-proportional schedule, computed once outside the loop
            # (which row writes the rounds need ride this gather as the
            # uniform ``any_alloc``/``any_write`` flags, §14)
            with jax.named_scope("kv.schedule"):
                (round_no, write_winner, any_alloc,
                 any_write) = self._service_schedule(
                    ops, keys, lock_id, ticket, want_lock)

        def _serve_rounds(st_s, pending0, succ0, ticket, round_no,
                          write_winner, any_alloc, any_write):
            def cond(c):
                _st, pending, _succ, _look, _r = c
                return jax.lax.psum(
                    jnp.any(pending).astype(jnp.int32), self.axis) > 0

            def body(c):
                st_c, pending, succ, look, r = c
                serve = None if round_no is None else (round_no == r)
                with self.mgr.no_tracking():
                    st_c, pending, _held, s_now, look = \
                        self._service_window(
                            st_c, ops, keys, values, lock_id, ticket,
                            pending, look, serve=serve,
                            write_winner=write_winner, homes=homes,
                            any_alloc=any_alloc, any_write=any_write)
                return st_c, pending, succ | s_now, look, r + 1

            return jax.lax.while_loop(
                cond, body, (st_s, pending0, succ0, look0, jnp.int32(1)))

        if lockfree:
            win_fast = plan["win_fast"]
            # a found UPDATE succeeds whether or not its write wins —
            # same success rule as the locked round
            do_upd_fast = (ops == UPDATE) & found0 & win_fast
            has_cache = self.cache is not None

            # the mutation prologue — prepared acquire, §8.3
            # invalidation and the fast serve — rides one 0/1-iteration
            # while_loop keyed on the (uniform) any_want scalar: a
            # pure-GET window skips it all, and the skipped iteration's
            # outputs are identities (zero ticket totals move no
            # counter, nothing to invalidate or write).  The carry holds
            # ONLY the leaves the prologue writes; the fallback service
            # rounds and the deferred release run outside (both are
            # no-ops for a skipped window: no pending lanes, release of
            # zero).
            def mut_body(c):
                _todo, locks, cache, rows, _ticket, _tot = c
                with jax.named_scope("kv.lock_acquire"):
                    lstate, ticket = self.locks.acquire_window_prepared(
                        locks, lock_id, want_lock, plan["rank"],
                        plan["totals"])
                lock_totals = lstate.next_ticket - locks.next_ticket
                # §8.3 coherence for fast windows: the locked rounds
                # piggyback the "row mutated" flag on their tracker
                # gather; the plan gathered the same (node, slot, flag)
                # columns, so peers invalidate identically.  A fallback
                # window's flags are masked here and re-gathered by its
                # service rounds.
                if has_cache:
                    cache = self.cache.invalidate(
                        cache, plan["inv_node"], plan["inv_slot"],
                        plan["inv_flag"] & win_fast)
                # fast serve: commuting UPDATEs are ONE batched counter-
                # validated one-sided write — value re-encoded with the
                # slot-reuse counter the index view returned (a stale
                # view would write a row readers reject; the ticket
                # counters say the window completed either way).  The
                # write rides its own 0/1-iteration while_loop keyed on
                # the (replicated-consistent) classification, so
                # ineligible windows never execute the collective;
                # superseded same-key lanes are winner-masked exactly
                # like the locked round's batched write.
                row_upd = jax.vmap(
                    lambda v, c2: self.encode_row(v, c2, True))(values,
                                                                ctr0)

                def fast_write(frows):
                    rows2, _ = self.rows_region.write_batch(
                        frows, node0.astype(jnp.int32),
                        slot0.astype(jnp.int32), row_upd,
                        preds=do_upd_fast & plan["write_winner"],
                        assume_unique=True)
                    return rows2

                with jax.named_scope("kv.service"):
                    rows = _run_if(win_fast, fast_write, rows)
                return (jnp.zeros((), jnp.bool_), lstate, cache, rows,
                        ticket, lock_totals)

            cache_in = st.cache if has_cache else jnp.zeros((), jnp.int32)
            with self.mgr.no_tracking():
                (_todo, lstate, cache_out, rows_out, ticket,
                 lock_totals) = jax.lax.while_loop(
                    lambda c: c[0], mut_body,
                    (any_want, st.locks, cache_in, st.rows,
                     jnp.zeros((B,), st.locks.next_ticket.dtype),
                     jnp.zeros_like(st.locks.next_ticket)))
            st = st._replace(locks=lstate, rows=rows_out)
            if has_cache:
                st = st._replace(cache=cache_out)
            round_no, write_winner = plan["round_no"], plan["write_winner"]
            any_alloc, any_write = plan["any_alloc"], plan["any_write"]
            pending0, succ0 = want_lock & ~win_fast, do_upd_fast
            if self.mgr.traffic.enabled:
                colls.record_fastpath(
                    self.mgr.traffic, self.full_name,
                    win_fast.astype(jnp.float32), 1.0)
        else:
            pending0 = want_lock
            succ0 = jnp.zeros((B,), jnp.bool_)

        with jax.named_scope("kv.service"):
            st, _pending, succ, _look, _r = _serve_rounds(
                st, pending0, succ0, ticket, round_no, write_winner,
                any_alloc, any_write)

        if not self.reference_impl:
            # deferred batched release: critical-section effects joined
            # first (one end-of-window release fence, §5.4), then every
            # lock's now_serving advances by its completed-ticket count
            with jax.named_scope("kv.release"):
                gate = join(AckKey([st.rows.buf]), True)
                ns = jnp.where(gate, st.locks.now_serving + lock_totals,
                               st.locks.now_serving)
                st = st._replace(locks=st.locks._replace(now_serving=ns))

        is_get = ops == GET
        return st, KVResult(
            value=jnp.where(is_get[:, None], get_val,
                            jnp.zeros((B, self.W), jnp.int32)),
            found=jnp.where(is_get, get_found, succ),
            retries=jnp.broadcast_to(retries, (B,)))

    # -- single-op round: the B=1 window ----------------------------------------------
    def op_round(self, st: KVStoreState, op, key, value):
        """Every participant submits one operation; runs service rounds until
        all complete.  Returns (state, KVResult).  This is the B=1 wrapper
        around :meth:`op_window`.

        op: () int32 in {NOP, GET, INSERT, UPDATE, DELETE}
        key: () uint32 (nonzero); value: (W,) int32.
        """
        st, res = self.op_window(
            st, jnp.reshape(jnp.asarray(op, jnp.int32), (1,)),
            jnp.reshape(jnp.asarray(key, jnp.uint32), (1,)),
            jnp.reshape(jnp.asarray(value, jnp.int32), (1, self.W)))
        return st, KVResult(value=res.value[0], found=res.found[0],
                            retries=res.retries[0])

    def _op_round_reference(self, st: KVStoreState, op, key, value):
        """Original scalar op_round — the executable specification.

        Kept verbatim (scalar `_get` + `_service_round`) so the regression
        suite can pin ``op_window`` with B=1 against it bit-for-bit; not a
        production entry point.
        """
        op = jnp.asarray(op, jnp.int32)
        key = jnp.asarray(key, jnp.uint32)
        value = jnp.asarray(value, jnp.int32).reshape(self.W)
        lock_id = (key % jnp.uint32(self.L)).astype(jnp.int32)
        want_lock = (op == INSERT) | (op == UPDATE) | (op == DELETE)
        lstate, ticket = self.locks.acquire(st.locks, lock_id, want_lock)
        st = st._replace(locks=lstate)

        # lock-free GET against pre-round state
        get_val, get_found, retries = self._get(st, key, op == GET)

        def cond(c):
            _st, pending, _succ = c
            return jax.lax.psum(pending.astype(jnp.int32), self.axis) > 0

        def body(c):
            st_c, pending, succ = c
            with self.mgr.no_tracking():
                st_c, pending, _held, s_now = self._service_round(
                    st_c, op, key, value, lock_id, ticket, pending)
            return st_c, pending, succ | s_now

        st, _pending, succ = jax.lax.while_loop(
            cond, body, (st, want_lock, jnp.asarray(False)))

        is_get = op == GET
        return st, KVResult(
            value=jnp.where(is_get, get_val, jnp.zeros((self.W,), jnp.int32)),
            found=jnp.where(is_get, get_found, succ),
            retries=retries)

    # -- online migration + rebalancing (the §10 locality tier) ----------------
    def migrate_window(self, st: KVStoreState, keys, dests, preds=None):
        """Re-home a (B,) lane window of live rows in one collective
        round-set: lane b moves ``keys[b]`` to node ``dests[b]``.

        Sugar for :meth:`op_window` with MOVE lanes — migrations ride the
        ordinary windowed mutation rounds (ticket locks, tracker waves,
        ack-gated writes) and therefore linearize with concurrent
        GET/INSERT/UPDATE/DELETE windows exactly like any mutation.
        Returns (state, moved (B,) bool): a lane fails (False) when the
        key is absent, when the destination's free stack is exhausted, or
        when the lane is pred-masked; a move to the key's CURRENT home
        succeeds with no effect.
        """
        keys = jnp.asarray(keys, jnp.uint32).reshape(-1)
        B = keys.shape[0]
        if preds is None:
            preds = jnp.ones((B,), jnp.bool_)
        ops = jnp.where(jnp.asarray(preds), jnp.int32(MOVE), jnp.int32(NOP))
        st, res = self.op_window(st, ops, keys,
                                 jnp.zeros((B, self.W), jnp.int32),
                                 targets=jnp.asarray(dests, jnp.int32)
                                 .reshape(B))
        return st, res.found

    def _migrate_reference(self, st: KVStoreState, keys, dests, preds=None):
        """Executable migration specification: the (B,) lanes run as B
        sequential single-lane MOVE windows (trace-unrolled), so each move
        flows one at a time through the already-pinned op_window
        machinery.  The regression suite pins :meth:`migrate_window`
        against this spec result-for-result (states may differ in slot
        assignment order when several lanes target one destination — the
        same latitude the windowed mutation paths already have vs their
        scalar specs)."""
        keys = jnp.asarray(keys, jnp.uint32).reshape(-1)
        B = keys.shape[0]
        dests = jnp.asarray(dests, jnp.int32).reshape(B)
        if preds is None:
            preds = jnp.ones((B,), jnp.bool_)
        preds = jnp.asarray(preds)
        moved = []
        for b in range(B):
            st, ok = self.migrate_window(st, keys[b:b + 1], dests[b:b + 1],
                                         preds=preds[b:b + 1])
            moved.append(ok[0])
        return st, jnp.stack(moved)

    def rebalance_proposals(self, st: KVStoreState, max_moves: int,
                            min_heat: float = 1.0, with_alts: bool = False):
        """Propose up to ``max_moves`` MOVEs for rows whose **dominant
        reader is remote** (§10.3), from the HotTracker's decayed
        counters.  Requires ``track_heat=True``.

        One heat all-gather, then pure local work on replicated state:
        every participant derives the identical global proposal list
        (the index and the gathered heat agree everywhere), scores each
        live index entry by (dominant-reader heat − current-home heat),
        and takes the top ``max_moves``.  Proposals are dealt round-robin
        to participants — proposal j rides lane j÷P of participant j%P —
        so the returned per-participant lanes partition the list.

        Returns (keys (B,), dests (B,), valid (B,)) with
        B = ceil(max_moves / P); invalid lanes are padding.  With
        ``with_alts=True`` additionally returns (alts (B,), alt_valid
        (B,)): the **second-hottest** reader of each proposed row, for
        the §10.3 backlog spill — a proposal whose dominant destination
        is full retries there instead of deferring.  ``alt_valid`` gates
        the spill on the alternative actually improving locality
        (alt heat ≥ ``min_heat``, strictly above the current home's, and
        a different node than the current home).
        """
        if self.hot is None:
            raise ValueError("rebalance needs a heat-tracked store "
                             "(track_heat=True)")
        me = colls.my_id(self.axis)
        B = -(-int(max_moves) // self.P)
        M = min(B * self.P, self.C)
        B = -(-M // self.P)
        g = self.hot.all_heat(st.heat)                   # (P, P·S)
        dom = jnp.argmax(g, axis=0).astype(jnp.int32)    # dominant reader
        dom_heat = jnp.max(g, axis=0)
        used = st.idx[:, IDX_STATE] == _USED
        node = jnp.clip(st.idx[:, IDX_NODE], 0, self.P - 1)
        lid = self.hot.line_of(node, st.idx[:, IDX_SLOT])
        home_heat = g[node, lid]
        want = used & (dom[lid] != node) & (dom_heat[lid] >= min_heat)
        score = jnp.where(want, dom_heat[lid] - home_heat, -1.0)
        top_score, top_pos = jax.lax.top_k(score, M)
        valid_all = top_score > 0.0
        keys_all = _i2u(st.idx[top_pos, IDX_KEY])
        dests_all = dom[lid[top_pos]]
        sel = jnp.clip(me + jnp.arange(B, dtype=jnp.int32) * self.P,
                       0, M - 1)
        # honor the caller's bound exactly: proposal indices at or past
        # max_moves are padding even when the padded lane grid (B·P)
        # rounds past it
        lane_ok = (me + jnp.arange(B, dtype=jnp.int32) * self.P) \
            < min(int(max_moves), M)
        if not with_alts:
            return (keys_all[sel], dests_all[sel], valid_all[sel] & lane_ok)
        # second-hottest reader per line: mask out the dominant reader's
        # row and re-take the argmax (same replicated arithmetic, so
        # every participant derives the identical alternates)
        g_wo = jnp.where(jnp.arange(self.P)[:, None] == dom[None, :],
                         -jnp.inf, g)
        alt = jnp.argmax(g_wo, axis=0).astype(jnp.int32)
        alt_heat = jnp.max(g_wo, axis=0)
        alts_all = alt[lid[top_pos]]
        altv_all = ((alt_heat[lid[top_pos]] >= min_heat)
                    & (alt_heat[lid[top_pos]] > home_heat[top_pos])
                    & (alts_all != node[top_pos]))
        return (keys_all[sel], dests_all[sel], valid_all[sel] & lane_ok,
                alts_all[sel], altv_all[sel])

    def rebalance(self, st: KVStoreState, max_moves: int,
                  min_heat: float = 1.0):
        """Propose and execute one migration window: rows whose dominant
        reader is remote move to that reader.  Returns (state, n_moved ()
        int32 — the cluster-wide count of executed moves).

        Proposals that fail to execute (destination free stack exhausted,
        key vacated mid-window) first **spill to the second-hottest
        reader** (§10.3 backlog spill): when that alternative also
        improves locality (see :meth:`rebalance_proposals`'s
        ``alt_valid``) the row moves there in a second migration window
        instead of waiting for the full destination to free space.  What
        still fails is **deferred, not dropped**: the heat evidence
        behind it persists, so the next ``rebalance()`` call re-proposes
        it.  The cluster-wide count of such deferrals is recorded in
        ``st.heat.backlog`` (surfaced as
        ``stats()["locality"]["migration_backlog"]`` by the engine) so a
        stuck migration — e.g. a perpetually full destination — is
        observable instead of indistinguishable from convergence."""
        keys, dests, valid, alts, altv = self.rebalance_proposals(
            st, max_moves, min_heat=min_heat, with_alts=True)
        st, moved = self.migrate_window(st, keys, dests, preds=valid)
        spill = valid & ~moved & altv
        st, spilled = self.migrate_window(st, keys, alts, preds=spill)
        n_prop = jax.lax.psum(jnp.sum(valid.astype(jnp.int32)), self.axis)
        n_moved = (jax.lax.psum(jnp.sum(moved.astype(jnp.int32)), self.axis)
                   + jax.lax.psum(jnp.sum(spilled.astype(jnp.int32)),
                                  self.axis))
        st = st._replace(heat=st.heat._replace(backlog=n_prop - n_moved))
        return st, n_moved

    # -- replication record export hook (DESIGN.md §9.3) ----------------------
    @property
    def record_width(self) -> int:
        """Width (int32 words) of one exported mutation record:
        ``[op | key_bits | value…W | home]`` — 5 for the default W=2,
        the same row shape as the (P·B, 5) tracker records the service
        rounds gather.  The trailing word carries the lane's resolved
        §10 home (placement/MOVE target after policy resolution)."""
        return 3 + self.W

    def export_window_records(self, ops, keys, values, targets=None):
        """Encode one (B,) window lane set as replication records.

        Returns (B, record_width) int32 rows ``[op | key_bits | value… |
        home]`` with non-mutating lanes (NOP/GET) masked to NOP —
        exactly the information a replica needs to replay the window's
        state effect: GETs mutate nothing, and every mutation's outcome is
        a deterministic function of (op, key, value, home) under the
        window's (participant, lane) order.  The trailing column carries
        the lane's **resolved §10 home** — the placement policy applied
        to (op, key, target) by the exporting participant, not the raw
        hint — so replay is *policy-independent*: a replica converges
        bitwise even if its own ``placement=`` knob differs from the
        leader's (the misconfiguration that would otherwise silently
        diverge).  This is the record-export hook the
        :class:`~repro.core.replog.ReplicatedLog` publishes per mutation
        window.
        """
        ops = jnp.asarray(ops, jnp.int32)
        B = ops.shape[0]
        keys = jnp.asarray(keys, jnp.uint32).reshape(B)
        values = jnp.asarray(values, jnp.int32).reshape(B, self.W)
        mut = (ops == INSERT) | (ops == UPDATE) | (ops == DELETE) \
            | (ops == MOVE)
        homes = self._lane_homes(ops, keys, targets)
        if homes is None:        # writer-local fast path: home IS the writer
            # ... and MOVE lanes are documented no-ops there, so their
            # records must be masked too — a follower replays through the
            # placed path and would otherwise execute a phantom move
            mut = mut & (ops != MOVE)
            homes = jnp.broadcast_to(colls.my_id(self.axis), (B,))
        return jnp.concatenate([
            jnp.where(mut, ops, NOP)[:, None], _u2i(keys)[:, None],
            values, homes.astype(jnp.int32)[:, None]], axis=1)

    def replay_window_records(self, st: KVStoreState, recs, pred=True):
        """Apply one exported (B, record_width) record lane set through
        :meth:`op_window` — the existing vectorized service machinery, so
        a replica's state evolves through exactly the leader's code path.
        ``pred=False`` masks the whole window to NOP lanes, which
        ``op_window`` executes as the identity (no locks wanted, zero
        service rounds) — an absent log entry replays as a no-op.

        The record's resolved-home column is threaded back in as the
        authoritative per-lane home (``targets_are_homes=True``), so
        replay runs the placed service path (§10) with the LEADER's
        placement decisions whatever path — or policy — the leader used;
        the paths commit identical state bits for identical windows,
        which the replication suites pin leaf-by-leaf.  Returns
        (state, KVResult)."""
        recs = jnp.asarray(recs, jnp.int32)
        ops = jnp.where(jnp.asarray(pred), recs[:, 0], NOP)
        return self.op_window(st, ops, _i2u(recs[:, 1]),
                              recs[:, 2:2 + self.W],
                              targets=recs[:, 2 + self.W],
                              targets_are_homes=True)

    # -- batched lock-free GETs (the paper's §7 "large window" mode) ---------
    def get_batch(self, st: KVStoreState, keys, pred=None):
        """R lock-free GETs per participant in ONE collective round.

        keys: (R,) uint32; ``pred``: optional (R,) bool lane mask (parity
        with ``_get_window``) — disabled lanes return zeros/not-found and
        cost nothing on the wire, so short batches need no dummy lanes.
        Returns (state, values (R, W), found (R,)): the state carries the
        read tier's refills and heat observations (and nothing else —
        GETs mutate no store data), so hot rows served this call are
        cache hits on the next and evidence for :meth:`rebalance`.

        This is the read-only corner of :meth:`op_window`: R outstanding
        one-sided reads amortize the request/serve round-trip — realized
        as a single coalesced remote read, short-circuited entirely when
        every lane hits the cache.
        """
        keys = jnp.asarray(keys, jnp.uint32)
        if pred is None:
            pred = jnp.ones(keys.shape, jnp.bool_)
        values, found, _tries, st = self._get_window(st, keys, pred)
        return st, values, found

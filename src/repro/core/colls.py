"""Collective building blocks for channel implementations.

These are the TPU-native realizations of LOCO's one-sided verbs (DESIGN.md
§2).  Each helper documents its collective cost so the roofline ledger and
the AckKey descriptors stay honest.

Locality tier (DESIGN.md §2.3): the batched verbs take per-lane ``preds``
and treat ``target == me`` lanes as **local memory accesses** — served from
``local_buf`` (reads) or applied from the local payload (writes) without
contributing to the gathered/reduced wire tensors.  Disabled lanes
contribute nothing either.  When a :class:`~repro.core.runtime.TrafficLedger`
is passed, every verb records its *modeled* wire bytes — counting only
enabled non-self lanes, so NUMA-style placement (the paper's headline
programming model) shows up as measured-zero traffic rather than being
silently priced like a remote access.

Read tier (DESIGN.md §8.1): the batched read verb coalesces duplicate
(target, index) pairs per participant before the wire — unique rows ride
the collective, duplicates fan out locally — so modeled read bytes scale
with unique remote rows, not lane count.

Conventions: all functions run inside a per-participant trace (under vmap or
shard_map) with collectives over ``axis``.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp


def verb(fn):
    """Name the ops of a verb that moves data between participants
    ``verb.<function name>`` in the compiled program's metadata, so a
    profiler trace can give the verb its device time.  A name scope is
    metadata only: the ops and their order are unchanged."""
    @functools.wraps(fn)
    def scoped(*args, **kwargs):
        with jax.named_scope(f"verb.{fn.__name__}"):
            return fn(*args, **kwargs)
    return scoped


def my_id(axis: str):
    return jax.lax.axis_index(axis)


def _item_nbytes(local_buf) -> int:
    """Static per-row payload bytes of a (slots, *item) buffer."""
    n = 1
    for d in local_buf.shape[1:]:
        n *= int(d)
    return n * local_buf.dtype.itemsize


def _record(ledger, verb, wire_bytes):
    """Report modeled wire bytes into the traffic ledger (no-op when
    disabled — a trace-time Python check, zero cost on the hot path)."""
    if ledger is not None and ledger.enabled:
        ledger.record(verb, wire_bytes)


def record_dma(ledger, verb, nbytes):
    """Report *measured* DMA-kernel bytes into the traffic ledger's
    measured tier (DESIGN.md §15) — counters the remote-DMA kernels
    compute from the same masks that drive their copies, kept separate
    from the modeled ``record`` rows so the roofline bench can assert
    the two agree.  Same trace-time gating as :func:`_record`."""
    if ledger is not None and ledger.enabled:
        ledger.record_dma(verb, nbytes)


def _dma():
    """The remote-DMA kernel module, imported lazily so the core verb
    layer does not drag the whole Pallas kernel package in for the
    backends that never touch it."""
    from ..kernels import remote_dma
    return remote_dma


def record_rounds(ledger, verb, rounds, axis: str):
    """Report modeled collective *rounds* into the traffic ledger
    (DESIGN.md §14).  A round is cluster-wide, but the per-participant
    trace fires one callback per participant — so only participant 0
    contributes a non-zero count, keeping the ledger total exact.  Same
    trace-time gating as :func:`_record`."""
    if ledger is not None and ledger.enabled:
        me = my_id(axis)
        ledger.record_rounds(
            verb, jnp.where(me == 0, jnp.float32(rounds), jnp.float32(0.0)))


def record_fastpath(ledger, name, fast, windows):
    """Report lock-skipped rounds into the traffic ledger (DESIGN.md §11):
    ``fast`` windows out of ``windows`` executed were classified commuting
    and served without any lock/tracker collectives.  Same trace-time
    gating as :func:`_record` — disabled ledgers cost nothing."""
    if ledger is not None and ledger.enabled:
        ledger.record_fastpath(name, fast, windows)


@verb
def bcast_from(value, owner, axis: str):
    """Broadcast ``value`` from participant ``owner`` to all participants.

    RDMA analogue: the owner's one-sided *push* of an owned_var (§5.1.1).
    Realized as a masked all-reduce: cost 2·|value| bytes on a ring,
    independent of P (cheaper than the P·|value| of an all-gather).
    ``owner`` may be traced.
    """
    me = my_id(axis)
    masked = jax.tree.map(
        lambda v: jnp.where(me == owner, v, jnp.zeros_like(v)), value)
    return jax.tree.map(lambda v: jax.lax.psum(v, axis), masked)


@verb
def gather_rows(value, axis: str):
    """All-gather each participant's ``value`` into a leading-P table.

    RDMA analogue: every owner pushes its register to every peer (the SST
    ``push_broadcast``).  Cost (P-1)/P·P·|value| ≈ P·|value| bytes per link.
    """
    return jax.lax.all_gather(value, axis, axis=0, tiled=False)


@verb
def prefix_sums(x, axis: str) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(exclusive_prefix_at_me, total, gathered) for scalar ``x`` per node.

    Used to resolve contended fetch-and-add deterministically: participant
    order is the arrival order (fair, like FIFO NIC service).  Implemented
    via a small all-gather — P words — then a local scan.
    """
    g = jax.lax.all_gather(x, axis, axis=0, tiled=False)  # (P,)
    me = my_id(axis)
    idx = jnp.arange(g.shape[0])
    excl = jnp.sum(jnp.where(idx < me, g, jnp.zeros_like(g)))
    total = jnp.sum(g)
    return excl, total, g


@verb
def window_prefix(x, axis: str) -> Tuple[jax.Array, jax.Array]:
    """(exclusive_prefix, total) for a (B,) lane vector per participant,
    flattened in **(participant, lane) lexicographic order** over all P·B
    lanes — the windowed generalization of :func:`prefix_sums`.

    ``excl[b]`` sums every lane (q, c) with q < me, plus my own lanes
    c < b; ``total`` sums all P·B lanes.  One (B,)-word all-gather plus a
    local scan — the single ranked prefix-scan that resolves a whole
    window of contended FAA requests (tickets, queue slots) in one
    round-set, preserving the scalar path's participant-order fairness
    lane-wise within each participant.
    """
    x = jnp.asarray(x)
    g = jax.lax.all_gather(x, axis, axis=0, tiled=False)        # (P, B)
    me = my_id(axis)
    qs = jnp.arange(g.shape[0])
    before_me = jnp.sum(jnp.where((qs < me)[:, None], g, jnp.zeros_like(g)))
    mine = jnp.cumsum(x) - x                                    # lane-local
    return before_me + mine, jnp.sum(g)


def remote_read(local_buf, target, index, axis: str, pred=True,
                ledger=None, verb: str = "remote_read"):
    """One-sided READ: each participant reads row ``index`` of participant
    ``target``'s ``local_buf``  →  (P_requests are served collectively).

    local_buf: (slots, *item)   per-participant storage
    target:    () int32         participant to read from (traced)
    index:     () int32         row within target's buffer (traced)
    pred:      () bool          disabled requests return zeros, cost nothing
    returns:   (*item,) value as stored at the target.

    Implementation ("NIC-served read"): requests are tiny (2 words) and are
    all-gathered; every participant serves the requests that address it; the
    served values return via a masked all-reduce.  Cost ≈ 2·P·|item| bytes
    (the reduce) + negligible request bytes — the collective analogue of P
    concurrent RDMA reads.  A ``target == me`` request is a *local* read
    (DESIGN.md §2.3): it is served from ``local_buf`` directly, masked out
    of the reduced table, and modeled at zero wire bytes.
    """
    me = my_id(axis)
    target = jnp.asarray(target, jnp.int32)
    index = jnp.asarray(index, jnp.int32)
    pred = jnp.asarray(pred)
    remote = pred & (target != me)
    req = jnp.stack([target, index, remote.astype(jnp.int32)])
    reqs = jax.lax.all_gather(req, axis, axis=0, tiled=False)      # (P, 3)
    tgt, idx, en = reqs[:, 0], reqs[:, 1], reqs[:, 2] != 0
    # serve every *remote* request addressed to me: (P, *item)
    served = local_buf[jnp.clip(idx, 0, local_buf.shape[0] - 1)]
    mine = (tgt == me) & en
    served = jnp.where(
        mine.reshape((-1,) + (1,) * (served.ndim - 1)), served,
        jnp.zeros_like(served))
    # return values: each requester picks its own row of the summed table.
    table = jax.lax.psum(served, axis)                              # (P, *item)
    out = table[me]
    # locality fast path: self-targeted reads come from local memory
    local_val = local_buf[jnp.clip(index, 0, local_buf.shape[0] - 1)]
    out = jnp.where(pred & (target == me), local_val, out)
    out = jnp.where(pred, out, jnp.zeros_like(out))
    _record(ledger, verb,
            2.0 * _item_nbytes(local_buf) * remote.astype(jnp.float32))
    record_rounds(ledger, verb, 2.0, axis)
    return out


def _serve_scatter(local_buf, targets, indices, wire_lane, axis: str,
                   engine=None):
    """The shared wire path of the batched read verbs: all-gather the (R,)
    read requests (a lane rides iff ``wire_lane``), serve the gathered
    requests addressed to me from ``local_buf``, and psum_scatter the
    (P, R, *item) served tensor back so requester q receives exactly its R
    answers.  Lanes with ``wire_lane == False`` contribute zeros to the
    reduce and come back as zero rows.  Returns (R, *item).

    With an ``engine`` (the Pallas DMA backend, DESIGN.md §15) the same
    wire path runs through the remote-DMA kernels: the requester builds
    (R, 8)-word transfer descriptors that ride the request gather in
    place of the 3-word tuples, the home serves the described rows with
    the gather kernel, and the engine records the *measured* bytes both
    kernels count.  The served values are bitwise those of the jnp path
    — only the lowering and the measured tier differ.
    """
    me = my_id(axis)
    R = targets.shape[0]
    if engine is None:
        req = jnp.stack([targets, indices, wire_lane.astype(jnp.int32)],
                        axis=-1)
        t_col, i_col, e_col = 0, 1, 2
    else:
        dma = _dma()
        req, desc_nb = dma.build_descriptors(
            targets, indices, wire_lane, op=dma.OP_READ,
            row_nbytes=_item_nbytes(local_buf))
        engine.count(desc_nb)
        t_col, i_col, e_col = 1, 2, 3
    reqs = jax.lax.all_gather(req, axis, axis=0, tiled=False)  # (P, R, 3|8)
    P = reqs.shape[0]
    tgt = reqs[..., t_col]
    idx = jnp.clip(reqs[..., i_col], 0, local_buf.shape[0] - 1)
    en = reqs[..., e_col] != 0
    if engine is None:
        served = local_buf[idx.reshape(-1)]                     # (P*R, *item)
        served = served.reshape((P, R) + local_buf.shape[1:])
        mask = ((tgt == me) & en).reshape(
            (P, R) + (1,) * (local_buf.ndim - 1))
        served = jnp.where(mask, served, jnp.zeros_like(served))
    else:
        buf2d = local_buf.reshape(local_buf.shape[0], -1)
        rows, served_nb = _dma().gather_rows(
            buf2d, idx.reshape(-1), ((tgt == me) & en).reshape(-1))
        engine.count(served_nb)
        served = rows.reshape((P, R) + local_buf.shape[1:])
    # psum_scatter over the requester axis: requester q receives sum_p served[p, q]
    return jax.lax.psum_scatter(served, axis, scatter_dimension=0, tiled=False)


def remote_read_batch(local_buf, targets, indices, axis: str, preds=None,
                      ledger=None, verb: str = "remote_read_batch",
                      coalesce: bool = True, engine=None, cost_fn=None):
    """Vector form of :func:`remote_read`: R requests per participant.

    targets, indices: (R,) int32; preds: (R,) bool (default all-enabled).
    Returns (R, *item).  Served via all-gather(requests) + local gather +
    psum_scatter of the (P, R, *item) served tensor — each participant
    receives exactly its R answers, so the wire cost is ≈ 2·P·R·|item| on a
    ring (reduce-scatter), not P²·R·|item|.

    By default this delegates to :func:`remote_read_coalesced`, which
    dedupes the (target, index) pairs per participant before the wire —
    modeled wire bytes scale with *unique* remote rows, not lane count
    (DESIGN.md §8.1).  ``coalesce=False`` keeps every enabled remote lane
    on the wire (the pre-coalescing cost model, retained for benchmarking).

    Locality tier (DESIGN.md §2.3): disabled lanes and ``target == me``
    lanes are masked out of the served tensor (they contribute zeros to the
    reduce and are modeled at zero wire bytes); self lanes are served from
    ``local_buf`` after the scatter, disabled lanes return zeros.

    ``engine`` routes the wire path through the remote-DMA kernels and
    records their measured bytes (DESIGN.md §15); ``cost_fn(n, nb)``
    overrides the *modeled* per-verb byte contract (n wire lanes of nb
    row bytes each) — the seam the Pallas backend's descriptor cost model
    plugs into.  Neither changes the returned values.
    """
    if coalesce:
        return remote_read_coalesced(local_buf, targets, indices, axis,
                                     preds=preds, ledger=ledger, verb=verb,
                                     engine=engine, cost_fn=cost_fn)
    me = my_id(axis)
    R = targets.shape[0]
    targets = targets.astype(jnp.int32)
    indices = indices.astype(jnp.int32)
    if preds is None:
        preds = jnp.ones((R,), jnp.bool_)
    preds = jnp.asarray(preds)
    self_lane = preds & (targets == me)
    remote_lane = preds & (targets != me)
    out = _serve_scatter(local_buf, targets, indices, remote_lane, axis,
                         engine=engine)
    # locality fast path: self lanes served from local memory, zero wire
    local_vals = local_buf[jnp.clip(indices, 0, local_buf.shape[0] - 1)]
    lane = (R,) + (1,) * (local_buf.ndim - 1)
    out = jnp.where(self_lane.reshape(lane), local_vals, out)
    out = jnp.where(preds.reshape(lane), out, jnp.zeros_like(out))
    nb = _item_nbytes(local_buf)
    n_wire = jnp.sum(remote_lane.astype(jnp.float32))
    _record(ledger, verb, cost_fn(n_wire, nb) if cost_fn is not None
            else 2.0 * nb * n_wire)
    record_rounds(ledger, verb, 2.0, axis)
    return out  # (R, *item)


def remote_read_coalesced(local_buf, targets, indices, axis: str, preds=None,
                          ledger=None, verb: str = "remote_read_coalesced",
                          engine=None, cost_fn=None):
    """Duplicate-coalescing batched read (DESIGN.md §8.1).

    Same contract as :func:`remote_read_batch`, but each participant's R
    lanes are deduplicated on (target, index) before the wire: the *first*
    enabled remote lane of each distinct pair (its **leader**) rides the
    all-gather/psum_scatter; duplicate lanes are masked out of the wire
    tensors and fan out locally from their leader's answer with one (R,)
    gather.  Bitwise-identical results to the uncoalesced path — reads
    commute and every duplicate observes the same served row.

    Leader election is O(R): a min-scatter of lane order into a
    (P·slots,) linear-row-id table (first lane wins), one gather back —
    no R² pairwise masks, so election stays cheap even when it is hoisted
    out of a caller's retry loop as loop-invariant code.

    Modeled wire bytes: 2·|item|·(unique enabled remote pairs) — a zipf
    window with R lanes over U distinct hot rows costs U rows, not R
    (the ~R/U reduction the read-tier benchmarks measure).  Self lanes and
    disabled lanes cost nothing, exactly as in the direct verb.
    """
    me = my_id(axis)
    R = targets.shape[0]
    targets = targets.astype(jnp.int32)
    indices = indices.astype(jnp.int32)
    if preds is None:
        preds = jnp.ones((R,), jnp.bool_)
    preds = jnp.asarray(preds)
    self_lane = preds & (targets == me)
    remote_lane = preds & (targets != me)
    # leader election via min-scatter on the linear row id: table[lid] =
    # first enabled remote lane addressing that row; lane i's
    # representative is table[lid_i], and i leads iff that is i itself.
    slots = local_buf.shape[0]
    n_rows = jax.lax.axis_size(axis) * slots
    order = jnp.arange(R, dtype=jnp.int32)
    lid = targets * slots + jnp.clip(indices, 0, slots - 1)
    table = jnp.full((n_rows,), R, jnp.int32).at[
        jnp.where(remote_lane, lid, n_rows)].min(order, mode="drop")
    rep = jnp.clip(table[lid], 0, R - 1)
    leader = remote_lane & (rep == order)
    out = _serve_scatter(local_buf, targets, indices, leader, axis,
                         engine=engine)
    # duplicate fan-out: every remote lane reads its leader's answer (a
    # leader's rep is itself, so this is the identity for leaders).
    lane = (R,) + (1,) * (local_buf.ndim - 1)
    out = jnp.where(remote_lane.reshape(lane), out[rep],
                    jnp.zeros_like(out))
    # locality fast path: self lanes served from local memory, zero wire
    local_vals = local_buf[jnp.clip(indices, 0, local_buf.shape[0] - 1)]
    out = jnp.where(self_lane.reshape(lane), local_vals, out)
    out = jnp.where(preds.reshape(lane), out, jnp.zeros_like(out))
    nb = _item_nbytes(local_buf)
    n_wire = jnp.sum(leader.astype(jnp.float32))
    _record(ledger, verb, cost_fn(n_wire, nb) if cost_fn is not None
            else 2.0 * nb * n_wire)
    record_rounds(ledger, verb, 2.0, axis)
    return out  # (R, *item)


def remote_write(local_buf, target, index, value, axis: str,
                 pred=True, ledger=None, verb: str = "remote_write"):
    """One-sided WRITE: each participant writes ``value`` into row ``index``
    of participant ``target``'s buffer.  Racy writes to the same row are
    resolved in participant order (lowest id last → highest id wins is
    avoided; we apply in increasing id so the *highest* id's write lands
    last, a fixed total order standing in for RDMA's unspecified outcome).

    Cost: all-gather of (P, *item) write payloads ≈ P·|item| bytes.  A
    ``target == me`` write is a local store (DESIGN.md §2.3): its payload is
    zeroed on the wire and applied from local memory, modeled at zero wire
    bytes.  Returns the updated local buffer.
    """
    me = my_id(axis)
    pred = jnp.asarray(pred)
    target = jnp.asarray(target, jnp.int32)
    self_lane = pred & (target == me)
    wire_value = jnp.where(self_lane, jnp.zeros_like(value), value)
    tgts = jax.lax.all_gather(target, axis, axis=0, tiled=False)    # (P,)
    idxs = jax.lax.all_gather(jnp.asarray(index, jnp.int32), axis,
                              axis=0, tiled=False)                  # (P,)
    vals = jax.lax.all_gather(wire_value, axis, axis=0, tiled=False)  # (P, *item)
    ens = jax.lax.all_gather(pred, axis, axis=0, tiled=False)       # (P,)
    # restore my own lane from local memory (it never rode the wire)
    vals = vals.at[me].set(value)

    def apply_one(buf, w):
        t, i, v, en = w
        do = (t == me) & en
        i = jnp.clip(i, 0, buf.shape[0] - 1)
        cur = buf[i]
        return buf.at[i].set(jnp.where(do, v, cur))

    P = tgts.shape[0]
    buf = local_buf
    # unrolled over P writers: deterministic order; P is a static mesh size.
    for w in range(P):
        buf = apply_one(buf, (tgts[w], idxs[w], vals[w], ens[w]))
    _record(ledger, verb, float(_item_nbytes(local_buf))
            * (pred & (target != me)).astype(jnp.float32))
    record_rounds(ledger, verb, 1.0, axis)
    return buf


def remote_write_batch(local_buf, targets, indices, values, axis: str,
                       preds=None, assume_unique=False, ledger=None,
                       verb: str = "remote_write_batch", engine=None,
                       cost_fn=None):
    """Vector form of :func:`remote_write`: R writes per participant,
    applied in (participant, request) lexicographic order.

    Cost: one all-gather of the (P, R, *item) payloads ≈ P·R·|item| bytes.
    Racy writes keep the fixed total order without a P·R sequential scatter
    chain: record k lands iff it is enabled, addresses me, and no enabled
    later record writes the same row ("last writer wins" computed as a
    winner mask), so all surviving writes land in ONE scatter.

    ``assume_unique=True`` skips the (P·R)² winner mask for callers that
    guarantee enabled writes never collide on a row (e.g. the kvstore,
    whose concurrent writers hold distinct locks on distinct live slots).

    Locality tier (DESIGN.md §2.3): ``target == me`` lanes are zeroed in
    the gathered payload tensor and applied from the local ``values`` array
    on arrival — a local store, modeled at zero wire bytes.  Disabled lanes
    cost nothing.

    ``engine`` routes the metadata gather and the commit through the
    remote-DMA kernels (DESIGN.md §15): (R, 8)-word descriptors ride the
    wire in place of the 3-word tuples, and the home commits the
    described rows with the scatter kernel, whose sequential lane-order
    application realizes the same last-writer-wins outcome as the winner
    mask — bitwise — without precomputing it (``assume_unique`` is
    irrelevant on that path).  ``cost_fn(n, nb)`` overrides the modeled
    byte contract exactly as in the read verbs.
    """
    R = targets.shape[0]
    targets = targets.astype(jnp.int32)
    if preds is None:
        preds = jnp.ones((R,), jnp.bool_)
    preds = jnp.asarray(preds)
    me = my_id(axis)
    self_lane = preds & (targets == me)
    remote_lane = preds & (targets != me)
    lane = (R,) + (1,) * (values.ndim - 1)
    wire_vals = jnp.where(self_lane.reshape(lane),
                          jnp.zeros_like(values), values)
    if engine is None:
        # one metadata all-gather: [target | index | pred] per request
        meta = jnp.stack([targets, indices.astype(jnp.int32),
                          preds.astype(jnp.int32)], axis=-1)            # (R,3)
        t_col, i_col, e_col = 0, 1, 2
    else:
        dma = _dma()
        meta, desc_nb = dma.build_descriptors(
            targets, indices, preds, wire=remote_lane, op=dma.OP_WRITE,
            row_nbytes=_item_nbytes(local_buf))                         # (R,8)
        engine.count(desc_nb)
        t_col, i_col, e_col = 1, 2, 3
    metas = jax.lax.all_gather(meta, axis, axis=0)                    # (P,R,·)
    vals = jax.lax.all_gather(wire_vals, axis, axis=0)                  # (P,R,*)
    # restore my own lanes from local memory (they never rode the wire)
    vals = vals.at[me].set(values)
    tgts, idxs = metas[..., t_col], metas[..., i_col]
    ens = metas[..., e_col] != 0
    P = tgts.shape[0]
    n = P * R
    flat_i = jnp.clip(idxs.reshape(n), 0, local_buf.shape[0] - 1)
    flat_v = vals.reshape((n,) + local_buf.shape[1:])
    win = (tgts.reshape(n) == me) & ens.reshape(n)
    nb = _item_nbytes(local_buf)
    n_wire = jnp.sum(remote_lane.astype(jnp.float32))
    _record(ledger, verb, cost_fn(n_wire, nb) if cost_fn is not None
            else float(nb) * n_wire)
    record_rounds(ledger, verb, 1.0, axis)
    if engine is not None:
        # DMA commit: lanes apply in sequence order; only lanes that came
        # from another participant count as measured wire payload.
        wire = win & (jnp.arange(n) // R != me)
        out2d, wire_nb = _dma().scatter_rows(
            local_buf.reshape(local_buf.shape[0], -1), flat_i,
            flat_v.reshape(n, -1), win, wire)
        engine.count(wire_nb)
        return out2d.reshape(local_buf.shape)
    if not assume_unique:
        order = jnp.arange(n)
        later_same = (flat_i[None, :] == flat_i[:, None]) & win[None, :] \
            & (order[None, :] > order[:, None])
        win = win & ~jnp.any(later_same, axis=1)
    # losers/disabled records get an out-of-range row and are dropped
    row = jnp.where(win, flat_i, local_buf.shape[0])
    return local_buf.at[row].set(flat_v, mode="drop")

"""Swappable execution backends for the one-sided verb layer (DESIGN.md §14).

LOCO exposes memory complexity so the programmer can pick the right
protocol per object, and "RDMA vs. RPC for Implementing Distributed Data
Structures" (PAPERS.md) shows neither one-sided verbs nor RPC-style
function shipping wins everywhere.  A :class:`CollsBackend` packages one
protocol contract behind the verb signatures of :mod:`repro.core.colls`,
so every channel (region, kvstore, queue, ringbuffer, cache, replog) and
the serving engine take a ``backend=`` knob instead of hard-wiring the
one-sided binding:

* ``onesided`` — the reference backend: the existing vmap/shard_map
  one-sided verbs, with their coalescing read tier and per-lane locality
  discounts.  Reads cost a request round plus a data round of
  2·|row|·unique bytes; writes push |row| bytes per remote lane.

* ``active_message`` — RPC-style function shipping: each window's ops
  ride the *request* gather to the home node as (header, payload)
  descriptors, the home applies them locally, and results return on the
  window's existing response scatter.  On the emulation substrate both
  protocols are realized by the same gather/serve/scatter collectives —
  ``_serve_scatter`` *is* "request gather → home apply → result
  scatter" — so the active-message backend reuses the one-sided
  execution math bitwise and swaps only the modeled wire contract:
  every op descriptor pays an :data:`AM_HDR_BYTES` header and ships
  un-coalesced (the home sees each RPC), but responses are direct sends
  (1·|row|, not 2·|row|) and the placed-path allocation decision ships
  *with* the op — the home allocates as part of applying, so the
  grant round-trip costs zero extra rounds (``alloc_rounds``).

* ``pallas`` — the remote-DMA lowering (DESIGN.md §15): the batched
  verbs run through the Pallas kernels in
  :mod:`repro.kernels.remote_dma` — requesters build fixed-width
  transfer descriptors that ride the request gather, homes serve/commit
  the described rows inside a kernel — and every kernel *measures* the
  bytes it moves, filed into the ledger's measured tier next to the
  modeled rows.  The modeled contract is RDMA-shaped: one
  :data:`DMA_DESC_BYTES` descriptor plus one |row| response per
  **unique coalesced** remote read (coalescing survives — the
  descriptor block is built after leader election), descriptor + |row|
  per remote write lane, and direct point-to-point payloads (1·|row|,
  not the one-sided model's 2·|row| read-back), over the same 2/1 round
  schedule and the same ``alloc_rounds = 2`` grant round-trip (DMA is
  one-sided — nothing ships to the home to fold the allocation into).

All backends record modeled wire bytes AND collective round counts into
the :class:`~repro.core.runtime.TrafficLedger`, which is what
``benchmarks/bench_crossover.py`` sweeps to find the crossover;
``benchmarks/bench_roofline.py`` pins the pallas backend's modeled rows
against its measured tier and against HLO-level collective accounting.
"""
from __future__ import annotations

import jax.numpy as jnp

from . import colls

#: Modeled bytes of one active-message op descriptor: verb tag, target,
#: index and length/flags words — the fixed RPC header every shipped op
#: pays regardless of payload width.
AM_HDR_BYTES = 16

#: Modeled bytes of one remote-DMA transfer descriptor (the NIC
#: work-queue entry): 8 int32 words of op/target/index/enable/length/seq
#: plus reserve.  Mirrors ``repro.kernels.remote_dma.DESC_BYTES`` — the
#: kernels count measured bytes with the same constant, and
#: tests/test_kernels.py pins the two equal so the cost model cannot
#: drift from the descriptor layout.  Kept as a literal here so the core
#: package does not import the kernel tier at module load.
DMA_DESC_BYTES = 32


class CollsBackend:
    """Protocol contract for the one-sided verb layer.

    Subclasses bind the four data verbs (scalar/batched read and write)
    plus the per-channel cost hooks.  Execution must be bitwise-identical
    across backends — the conformance suite (tests/test_backends.py)
    pins that — only the modeled wire bytes and round counts may differ.
    """

    name = "abstract"
    #: rounds the placed-path slot-allocation round-trip costs on top of
    #: the schedule gather (kvstore §10; 0 when the decision ships with
    #: the op, as in active-message function shipping).
    alloc_rounds = 2.0

    # -- data verbs ---------------------------------------------------------
    def read(self, local_buf, target, index, axis, pred=True,
             ledger=None, verb="remote_read"):
        raise NotImplementedError

    def read_batch(self, local_buf, targets, indices, axis, preds=None,
                   ledger=None, verb="remote_read_batch", coalesce=True):
        raise NotImplementedError

    def write(self, local_buf, target, index, value, axis, pred=True,
              ledger=None, verb="remote_write"):
        raise NotImplementedError

    def write_batch(self, local_buf, targets, indices, values, axis,
                    preds=None, assume_unique=False, ledger=None,
                    verb="remote_write_batch"):
        raise NotImplementedError

    # -- cost hooks ---------------------------------------------------------
    def record_publish(self, ledger, verb, slot_nbytes, n_moved, axis):
        """Ledger model of a ringbuffer publish of ``n_moved`` slots."""
        raise NotImplementedError

    def row_read_bytes(self, row_nbytes: int) -> float:
        """Modeled wire bytes of one remote row read (the serving
        engine's per-page cost constant)."""
        raise NotImplementedError


class OneSidedBackend(CollsBackend):
    """The reference backend: LOCO's one-sided verbs as realized today.

    Delegates straight to :mod:`repro.core.colls`, whose verbs record
    their own byte model (coalesced reads = 2·|row|·unique, locality
    discounts) and round counts (reads 2, writes 1)."""

    name = "onesided"
    alloc_rounds = 2.0

    def read(self, local_buf, target, index, axis, pred=True,
             ledger=None, verb="remote_read"):
        return colls.remote_read(local_buf, target, index, axis, pred=pred,
                                 ledger=ledger, verb=verb)

    def read_batch(self, local_buf, targets, indices, axis, preds=None,
                   ledger=None, verb="remote_read_batch", coalesce=True):
        return colls.remote_read_batch(local_buf, targets, indices, axis,
                                       preds=preds, ledger=ledger, verb=verb,
                                       coalesce=coalesce)

    def write(self, local_buf, target, index, value, axis, pred=True,
              ledger=None, verb="remote_write"):
        return colls.remote_write(local_buf, target, index, value, axis,
                                  pred=pred, ledger=ledger, verb=verb)

    def write_batch(self, local_buf, targets, indices, values, axis,
                    preds=None, assume_unique=False, ledger=None,
                    verb="remote_write_batch"):
        return colls.remote_write_batch(local_buf, targets, indices, values,
                                        axis, preds=preds,
                                        assume_unique=assume_unique,
                                        ledger=ledger, verb=verb)

    def record_publish(self, ledger, verb, slot_nbytes, n_moved, axis):
        # one-sided: the owner pushes each slot, consumers validate by
        # counter read-back — 2·|slot| per moved slot, one round.
        colls._record(ledger, verb, 2.0 * slot_nbytes
                      * jnp.asarray(n_moved, jnp.float32))
        colls.record_rounds(ledger, verb, 1.0, axis)

    def row_read_bytes(self, row_nbytes: int) -> float:
        return 2.0 * row_nbytes


class ActiveMessageBackend(CollsBackend):
    """RPC-style function shipping over the same window machinery.

    Ops execute through the identical gather/serve/scatter collectives as
    the one-sided backend (``ledger=None`` on the delegated call — the
    one-sided byte model must not fire), then this class records the
    active-message wire contract:

    * every enabled remote op ships an (:data:`AM_HDR_BYTES` + |row|)
      descriptor to its home — NO coalescing: the home node sees each
      RPC, so read bytes scale with lane count, not unique rows;
    * read responses are direct 1·|row| sends folded into the header+row
      request cost above (total (hdr+row)·lanes vs one-sided
      2·row·unique), over the same 2 rounds (request, response);
    * write completions piggyback on the window's existing ack round —
      1 round, (hdr+row)·lanes;
    * the placed-path allocation decision ships with the op: the home
      allocates while applying, so ``alloc_rounds`` is 0 (the one-sided
      backend pays a 2-round grant round-trip).
    """

    name = "active_message"
    alloc_rounds = 0.0

    def _op_bytes(self, local_buf, n_remote):
        return float(AM_HDR_BYTES + colls._item_nbytes(local_buf)) \
            * jnp.asarray(n_remote, jnp.float32)

    def read(self, local_buf, target, index, axis, pred=True,
             ledger=None, verb="remote_read"):
        out = colls.remote_read(local_buf, target, index, axis, pred=pred,
                                ledger=None, verb=verb)
        me = colls.my_id(axis)
        remote = jnp.asarray(pred) & (jnp.asarray(target, jnp.int32) != me)
        colls._record(ledger, verb, self._op_bytes(local_buf, remote))
        colls.record_rounds(ledger, verb, 2.0, axis)
        return out

    def read_batch(self, local_buf, targets, indices, axis, preds=None,
                   ledger=None, verb="remote_read_batch", coalesce=True):
        out = colls.remote_read_batch(local_buf, targets, indices, axis,
                                      preds=preds, ledger=None, verb=verb,
                                      coalesce=coalesce)
        me = colls.my_id(axis)
        if preds is None:
            preds = jnp.ones(targets.shape[:1], jnp.bool_)
        remote = jnp.asarray(preds) & (targets.astype(jnp.int32) != me)
        colls._record(ledger, verb,
                      self._op_bytes(local_buf, jnp.sum(remote)))
        colls.record_rounds(ledger, verb, 2.0, axis)
        return out

    def write(self, local_buf, target, index, value, axis, pred=True,
              ledger=None, verb="remote_write"):
        buf = colls.remote_write(local_buf, target, index, value, axis,
                                 pred=pred, ledger=None, verb=verb)
        me = colls.my_id(axis)
        remote = jnp.asarray(pred) & (jnp.asarray(target, jnp.int32) != me)
        colls._record(ledger, verb, self._op_bytes(local_buf, remote))
        colls.record_rounds(ledger, verb, 1.0, axis)
        return buf

    def write_batch(self, local_buf, targets, indices, values, axis,
                    preds=None, assume_unique=False, ledger=None,
                    verb="remote_write_batch"):
        buf = colls.remote_write_batch(local_buf, targets, indices, values,
                                       axis, preds=preds,
                                       assume_unique=assume_unique,
                                       ledger=None, verb=verb)
        me = colls.my_id(axis)
        if preds is None:
            preds = jnp.ones(targets.shape[:1], jnp.bool_)
        remote = jnp.asarray(preds) & (targets.astype(jnp.int32) != me)
        colls._record(ledger, verb,
                      self._op_bytes(local_buf, jnp.sum(remote)))
        colls.record_rounds(ledger, verb, 1.0, axis)
        return buf

    def record_publish(self, ledger, verb, slot_nbytes, n_moved, axis):
        # active message: the owner ships one (hdr + slot) message per
        # moved slot; delivery is the apply, no counter read-back.
        colls._record(ledger, verb, float(AM_HDR_BYTES + slot_nbytes)
                      * jnp.asarray(n_moved, jnp.float32))
        colls.record_rounds(ledger, verb, 1.0, axis)

    def row_read_bytes(self, row_nbytes: int) -> float:
        return float(AM_HDR_BYTES + row_nbytes)


class _DmaEngine:
    """Measured-byte sink the Pallas backend threads through the colls
    wire path: the remote-DMA kernels report the bytes they actually
    moved (descriptors emitted, rows served/committed — computed from
    the same masks that drive the copies) and the engine files them
    under the verb in the ledger's measured tier (§15).  Gating follows
    :func:`repro.core.colls.record_dma`: a disabled or absent ledger
    costs nothing at trace time."""

    __slots__ = ("ledger", "verb")

    def __init__(self, ledger, verb):
        self.ledger = ledger
        self.verb = verb

    def count(self, nbytes):
        colls.record_dma(self.ledger, self.verb, nbytes)


class PallasDmaBackend(CollsBackend):
    """One-sided verbs lowered onto Pallas remote-DMA kernels (§15).

    Execution: the batched verbs delegate to :mod:`repro.core.colls`
    with a :class:`_DmaEngine`, which swaps the wire path's jnp
    serve/commit for the :mod:`repro.kernels.remote_dma` kernels —
    descriptor build on the requester, row gather/scatter on the home —
    while the inter-participant hop is the XLA collective on every
    substrate, TPU included.  Values
    are bitwise those of the one-sided backend — the conformance suite
    pins it — and the scalar verbs route through the R=1 batch path so
    every verb rides the kernels.

    Cost model: each remote transfer pays a :data:`DMA_DESC_BYTES`
    work-queue descriptor plus a direct 1·|row| payload.  Reads coalesce
    (descriptors are built per elected leader lane), so read bytes are
    (desc + row)·unique vs the one-sided 2·row·unique and the
    active-message (hdr + row)·lanes; writes pay (desc + row)·lane over
    the usual 1 round; publishes push (desc + slot)·moved with delivery
    confirmed by the DMA completion, not a counter read-back.  Rounds
    match the one-sided schedule (request/response = 2, write = 1,
    ``alloc_rounds = 2``): DMA is still one-sided, so nothing ships to
    the home that could fold the allocation grant into the op.

    Every verb additionally records the kernels' *measured* bytes into
    the ledger's ``dma_counts`` tier — ``bench_roofline.py`` asserts
    modeled == measured within a pinned tolerance.
    """

    name = "pallas"
    alloc_rounds = 2.0

    @staticmethod
    def _cost_fn(n_lanes, row_nbytes):
        return float(DMA_DESC_BYTES + row_nbytes) * n_lanes

    def read(self, local_buf, target, index, axis, pred=True,
             ledger=None, verb="remote_read"):
        out = self.read_batch(
            local_buf,
            jnp.reshape(jnp.asarray(target, jnp.int32), (1,)),
            jnp.reshape(jnp.asarray(index, jnp.int32), (1,)),
            axis, preds=jnp.reshape(jnp.asarray(pred, jnp.bool_), (1,)),
            ledger=ledger, verb=verb)
        return out[0]

    def read_batch(self, local_buf, targets, indices, axis, preds=None,
                   ledger=None, verb="remote_read_batch", coalesce=True):
        return colls.remote_read_batch(
            local_buf, targets, indices, axis, preds=preds, ledger=ledger,
            verb=verb, coalesce=coalesce, engine=_DmaEngine(ledger, verb),
            cost_fn=self._cost_fn)

    def write(self, local_buf, target, index, value, axis, pred=True,
              ledger=None, verb="remote_write"):
        return self.write_batch(
            local_buf,
            jnp.reshape(jnp.asarray(target, jnp.int32), (1,)),
            jnp.reshape(jnp.asarray(index, jnp.int32), (1,)),
            value[None], axis,
            preds=jnp.reshape(jnp.asarray(pred, jnp.bool_), (1,)),
            ledger=ledger, verb=verb)

    def write_batch(self, local_buf, targets, indices, values, axis,
                    preds=None, assume_unique=False, ledger=None,
                    verb="remote_write_batch"):
        # assume_unique is moot on this path: the scatter kernel commits
        # lanes sequentially, realizing last-writer-wins natively.
        return colls.remote_write_batch(
            local_buf, targets, indices, values, axis, preds=preds,
            assume_unique=assume_unique, ledger=ledger, verb=verb,
            engine=_DmaEngine(ledger, verb), cost_fn=self._cost_fn)

    def record_publish(self, ledger, verb, slot_nbytes, n_moved, axis):
        # DMA publish: one descriptor + slot payload per moved slot,
        # delivery confirmed by the DMA completion (no counter
        # read-back), one round.
        colls._record(ledger, verb, float(DMA_DESC_BYTES + slot_nbytes)
                      * jnp.asarray(n_moved, jnp.float32))
        colls.record_rounds(ledger, verb, 1.0, axis)

    def row_read_bytes(self, row_nbytes: int) -> float:
        return float(DMA_DESC_BYTES + row_nbytes)


#: Singleton registry — backends are stateless, one instance each.
BACKENDS = {
    "onesided": OneSidedBackend(),
    "active_message": ActiveMessageBackend(),
    "pallas": PallasDmaBackend(),
}


def get_backend(spec=None, default=None):
    """Resolve a backend knob: a name from :data:`BACKENDS`, an instance
    (passed through), or ``None`` → ``default`` (itself resolved; the
    final fallback is the one-sided reference backend)."""
    if spec is None:
        if default is None:
            return BACKENDS["onesided"]
        return get_backend(default)
    if isinstance(spec, CollsBackend):
        return spec
    try:
        return BACKENDS[spec]
    except KeyError:
        raise ValueError(
            f"unknown colls backend {spec!r}; available: "
            f"{sorted(BACKENDS)}") from None

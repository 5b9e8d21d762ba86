"""Participant runtime and manager — LOCO's connection/resource manager.

The paper's ``loco::manager`` (§4.2) establishes connections, mediates
access to per-node resources (queue pairs, completion queue, registered
network memory) and hosts the join/connect protocol.  In the SPMD/XLA
adaptation:

* cluster membership is the **participant axis** of a JAX mesh (production)
  or a vmapped leading axis (single-process testing).  Both bindings run the
  *same* channel code, written against ``jax.lax`` collectives over an axis
  name — the channel endpoint is the per-participant trace.
* the join/connect wire protocol collapses to constructor-time registration:
  channel names are checked for uniqueness, sub-channels are namespaced under
  their parents with '/', and declared memory regions are recorded for the
  memory ledger (the analogue of libibverbs region registration + the 1 GB
  hugepage pool of Appendix A.2).
* the completion queue + polling thread are replaced by XLA data
  dependencies; the manager tracks outstanding :class:`AckKey`s per trace so
  ``fence`` can join the minimal token set for the requested scope.
"""
from __future__ import annotations

import contextlib
import os
import threading
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .ack import ALL_PEERS, AckKey, FenceScope, join


class Runtime:
    """Binds per-participant channel programs to an execution substrate.

    ``mesh=None``  → ``jax.vmap(axis_name=axis)`` over a stacked leading axis
                     (single-device functional simulation; used by tests).
    ``mesh=Mesh``  → ``jax.shard_map`` over ``axis`` of the mesh (production);
                     per-leaf local blocks of size 1 on the participant axis
                     are squeezed so channel code sees identical shapes under
                     both bindings.
    """

    def __init__(self, num_participants: int, axis: str = "nodes",
                 mesh: Optional[jax.sharding.Mesh] = None):
        self.P = int(num_participants)
        self.axis = axis
        self.mesh = mesh
        if mesh is not None:
            if mesh.shape[axis] != self.P:
                raise ValueError(
                    f"mesh axis {axis!r} has {mesh.shape[axis]} devices, "
                    f"but runtime expects {self.P} participants")

    # -- binding ------------------------------------------------------------
    def run(self, fn: Callable, *args):
        """Execute ``fn`` once per participant over stacked ``args``.

        Every leaf of ``args`` must have a leading axis of size P; ``fn``
        receives per-participant views without that axis and returns
        per-participant outputs, which come back stacked.
        """
        if self.mesh is None:
            return jax.vmap(fn, axis_name=self.axis)(*args)

        from jax.sharding import PartitionSpec as P  # local import: cheap

        spec = P(self.axis)

        def local_fn(*local_args):
            squeezed = jax.tree.map(lambda x: jnp.squeeze(x, 0), local_args)
            out = fn(*squeezed)
            return jax.tree.map(lambda x: jnp.expand_dims(jnp.asarray(x), 0), out)

        shmapped = jax.shard_map(
            local_fn, mesh=self.mesh,
            in_specs=jax.tree.map(lambda _: spec, args), out_specs=spec,
            check_vma=False)
        return shmapped(*args)

    # -- helpers used by channel code (inside the per-participant trace) ----
    def my_id(self):
        return jax.lax.axis_index(self.axis)

    def stack(self, per_participant_values: List[Any]):
        """Stack host-side per-participant values into runtime input layout."""
        return jax.tree.map(lambda *xs: jnp.stack(xs), *per_participant_values)


@dataclass
class RegionInfo:
    """Ledger entry for a declared network-memory region (Appendix A.2)."""

    name: str
    shape: tuple
    dtype: Any
    nbytes: int


class TrafficLedger:
    """Per-verb modeled wire-byte accounting (DESIGN.md §2.3).

    The one-sided verbs in :mod:`repro.core.colls` report the *modeled*
    bytes each call would put on the wire — counting only enabled,
    non-self-targeted lanes, so locality-placed accesses (``target == me``)
    are measured at zero, keeping the roofline story honest about the
    paper's NUMA-style placement claim.

    Recording happens through ``jax.debug.callback`` with a traced scalar,
    so the counts reflect runtime predicates (which lanes were actually
    enabled / self-targeted), not static worst cases.  The ledger is
    **disabled by default** and the enable check happens at *trace* time:
    callables jitted while the ledger is disabled carry no callbacks and
    pay nothing.  To account a workload, call :meth:`enable` and build a
    fresh jitted callable (a previously traced one will not re-trace).

    Under the vmap binding the callback fires once per participant, so
    totals are cluster-wide wire bytes (each participant accounts its own
    outgoing lanes exactly once).  The runtime may run those callbacks on
    several threads at once, so every update holds ``_lock``: an
    unguarded ``+=`` would lose increments depending on thread timing.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self.enabled = False
        self.counts: Dict[str, Dict[str, float]] = {}
        # modeled collective-round counters (DESIGN.md §14), keyed by verb
        # — kept separate from ``counts`` so the byte rows stay exactly as
        # existing assertions expect.  Only participant 0 contributes (see
        # colls.record_rounds), so totals are cluster-wide rounds.
        self.round_counts: Dict[str, Dict[str, float]] = {}
        # read-tier hit/lookup counters (DESIGN.md §8.2), keyed by channel
        self.cache_counts: Dict[str, Dict[str, float]] = {}
        # lock-skipped-round counters (DESIGN.md §11), keyed by channel:
        # windows classified lock-free vs windows that fell back to the
        # locked schedule
        self.fastpath_counts: Dict[str, Dict[str, float]] = {}
        # integrity/fencing event counters (DESIGN.md §12), keyed by
        # channel: slots that failed checksum validation on receive, and
        # stale-epoch entries rejected by the failover fence
        self.corrupt_counts: Dict[str, float] = {}
        self.fenced_counts: Dict[str, float] = {}
        # *measured* DMA-kernel bytes (DESIGN.md §15), keyed by verb —
        # counters the remote-DMA kernels compute from the same masks
        # that drive their copies.  Kept separate from the modeled
        # ``counts`` rows precisely so the roofline bench can assert the
        # two tiers agree instead of one silently defining the other.
        self.dma_counts: Dict[str, Dict[str, float]] = {}

    def enable(self):
        self.enabled = True
        return self

    def disable(self):
        self.enabled = False
        return self

    def reset(self):
        self.counts = {}
        self.round_counts = {}
        self.cache_counts = {}
        self.fastpath_counts = {}
        self.corrupt_counts = {}
        self.fenced_counts = {}
        self.dma_counts = {}
        return self

    def record(self, verb: str, wire_bytes):
        """Record ``wire_bytes`` (a traced scalar) against ``verb``.

        Must be called inside a trace; colls verbs gate on ``enabled``
        before calling so disabled ledgers never emit callbacks.
        """
        def _cb(b, verb=verb):
            with self._lock:
                entry = self.counts.setdefault(
                    verb, {"calls": 0, "bytes": 0.0})
                entry["calls"] += 1
                entry["bytes"] += float(b)

        jax.debug.callback(_cb, jnp.asarray(wire_bytes, jnp.float32))

    def record_rounds(self, verb: str, rounds):
        """Record modeled collective ``rounds`` (a traced scalar) against
        ``verb`` — the §14 protocol round counter.  Callers route through
        :func:`repro.core.colls.record_rounds`, which both gates on
        ``enabled`` at trace time and zeroes every participant but 0, so
        the accumulated total is exact cluster-wide rounds."""
        def _cb(r, verb=verb):
            with self._lock:
                e = self.round_counts.setdefault(verb, {"rounds": 0.0})
                e["rounds"] += float(r)

        jax.debug.callback(_cb, jnp.asarray(rounds, jnp.float32))

    def record_dma(self, verb: str, nbytes):
        """Record *measured* remote-DMA kernel bytes (a traced scalar)
        against ``verb`` — the §15 measured tier.  Callers route through
        :func:`repro.core.colls.record_dma`, which gates on ``enabled``
        at trace time; each participant counts the descriptor bytes it
        emits and the row bytes it serves/commits, so totals are
        cluster-wide wire bytes counted exactly once."""
        def _cb(b, verb=verb):
            with self._lock:
                e = self.dma_counts.setdefault(
                    verb, {"calls": 0, "bytes": 0.0})
                e["calls"] += 1
                e["bytes"] += float(b)

        jax.debug.callback(_cb, jnp.asarray(nbytes, jnp.float32))

    def record_cache(self, name: str, hits, lookups):
        """Record read-cache ``hits`` out of ``lookups`` (traced scalars)
        against channel ``name``.  Same trace-time gating contract as
        :meth:`record`: callers check ``enabled`` before calling, so
        disabled ledgers never emit callbacks."""
        def _cb(h, lk, name=name):
            with self._lock:
                e = self.cache_counts.setdefault(
                    name, {"hits": 0.0, "lookups": 0.0})
                e["hits"] += float(h)
                e["lookups"] += float(lk)

        jax.debug.callback(_cb, jnp.asarray(hits, jnp.float32),
                           jnp.asarray(lookups, jnp.float32))

    def record_fastpath(self, name: str, fast, windows):
        """Record ``fast`` lock-free-served windows out of ``windows``
        executed (traced scalars) against channel ``name`` — the §11
        lock-skipped-round ledger rows.  Same trace-time gating contract
        as :meth:`record`: callers check ``enabled`` before calling, so
        disabled ledgers never emit callbacks."""
        def _cb(f, w, name=name):
            with self._lock:
                e = self.fastpath_counts.setdefault(
                    name, {"fast_windows": 0.0, "windows": 0.0})
                e["fast_windows"] += float(f)
                e["windows"] += float(w)

        jax.debug.callback(_cb, jnp.asarray(fast, jnp.float32),
                           jnp.asarray(windows, jnp.float32))

    def record_corrupt(self, name: str, count):
        """Record ``count`` checksum-validation failures (a traced scalar)
        against channel ``name`` — a receive found a slot whose seq
        matched the cursor but whose checksum did not (torn/corrupted
        data, DESIGN.md §12): the re-read that used to happen silently is
        now a counted event.  Same trace-time gating contract as
        :meth:`record`: callers check ``enabled`` before calling, so
        disabled ledgers never emit callbacks."""
        def _cb(n, name=name):
            with self._lock:
                self.corrupt_counts[name] = \
                    self.corrupt_counts.get(name, 0.0) + float(n)

        jax.debug.callback(_cb, jnp.asarray(count, jnp.float32))

    def record_fenced(self, name: str, count):
        """Record ``count`` stale-epoch entries rejected by the failover
        fence (DESIGN.md §12.1) against channel ``name`` — a zombie
        writer's delayed publish was consumed-but-dropped.  Same
        trace-time gating contract as :meth:`record`."""
        def _cb(n, name=name):
            with self._lock:
                self.fenced_counts[name] = \
                    self.fenced_counts.get(name, 0.0) + float(n)

        jax.debug.callback(_cb, jnp.asarray(count, jnp.float32))

    def total_bytes(self) -> float:
        return sum(e["bytes"] for e in self.counts.values())

    def total_rounds(self) -> float:
        return sum(e["rounds"] for e in self.round_counts.values())

    def summary(self) -> Dict[str, Dict[str, float]]:
        return {k: dict(v) for k, v in sorted(self.counts.items())}

    def rounds_summary(self) -> Dict[str, Dict[str, float]]:
        """Per-verb modeled collective-round counts (§14)."""
        return {k: dict(v) for k, v in sorted(self.round_counts.items())}

    def dma_summary(self) -> Dict[str, Dict[str, float]]:
        """Per-verb *measured* DMA-kernel byte counts (§15)."""
        return {k: dict(v) for k, v in sorted(self.dma_counts.items())}

    def total_dma_bytes(self) -> float:
        return sum(e["bytes"] for e in self.dma_counts.values())

    def cache_summary(self) -> Dict[str, Dict[str, float]]:
        """Per-channel read-tier counters with derived hit rates."""
        out = {}
        for k, v in sorted(self.cache_counts.items()):
            e = dict(v)
            e["hit_rate"] = (v["hits"] / v["lookups"]) if v["lookups"] else 0.0
            out[k] = e
        return out

    def fastpath_summary(self) -> Dict[str, Dict[str, float]]:
        """Per-channel lock-skipped-round counters with derived rates."""
        out = {}
        for k, v in sorted(self.fastpath_counts.items()):
            e = dict(v)
            e["fast_rate"] = (v["fast_windows"] / v["windows"]) \
                if v["windows"] else 0.0
            out[k] = e
        return out

    def corrupt_summary(self) -> Dict[str, float]:
        """Per-channel checksum-validation-failure counts (§12)."""
        return dict(sorted(self.corrupt_counts.items()))

    def fenced_summary(self) -> Dict[str, float]:
        """Per-channel stale-epoch fenced-entry counts (§12.1)."""
        return dict(sorted(self.fenced_counts.items()))


class _TraceCtx(threading.local):
    def __init__(self):
        self.outstanding: List[AckKey] = []
        self.active = False


class Manager:
    """LOCO manager: channel registry, memory ledger, fence provider.

    ``backend`` selects the default execution protocol for every channel
    built under this manager (DESIGN.md §14): a name from
    :data:`repro.core.backends.BACKENDS` ("onesided", "active_message"),
    a :class:`~repro.core.backends.CollsBackend` instance, or ``None`` for
    the ``REPRO_DEFAULT_BACKEND`` environment default (falling back to
    the one-sided reference backend).  Channels may override per-object —
    the paper's pick-the-right-protocol-per-object stance.
    """

    def __init__(self, runtime: Runtime, backend=None):
        from .backends import get_backend  # local import: avoids a cycle
        self.runtime = runtime
        self.backend = get_backend(
            backend, default=os.environ.get("REPRO_DEFAULT_BACKEND"))
        self.channels: Dict[str, Any] = {}
        self.regions: Dict[str, RegionInfo] = {}
        self._trace = _TraceCtx()
        # fence statistics (static, per-trace) — reported by benchmarks
        self.fence_counts = {s: 0 for s in FenceScope}
        # modeled wire traffic per verb (DESIGN.md §2.3); disabled by default
        self.traffic = TrafficLedger()

    # -- registry (join/connect analogue) -----------------------------------
    @property
    def P(self) -> int:
        return self.runtime.P

    @property
    def axis(self) -> str:
        return self.runtime.axis

    def register_channel(self, full_name: str, channel: Any):
        if full_name in self.channels:
            raise ValueError(f"channel name collision: {full_name!r} "
                             "(join would fail: duplicate endpoint)")
        self.channels[full_name] = channel

    def register_region(self, full_name: str, shape, dtype):
        if full_name in self.regions:
            raise ValueError(f"memory region collision: {full_name!r}")
        nbytes = int(np.prod(shape)) * np.dtype(dtype).itemsize
        self.regions[full_name] = RegionInfo(full_name, tuple(shape), dtype, nbytes)
        return self.regions[full_name]

    def memory_ledger_bytes(self) -> int:
        """Total registered network memory per participant (hugepage pool)."""
        return sum(r.nbytes for r in self.regions.values())

    def traffic_ledger_bytes(self) -> float:
        """Total modeled wire bytes recorded by the traffic ledger
        (cluster-wide; 0.0 while the ledger is disabled)."""
        return self.traffic.total_bytes()

    # -- outstanding-op tracking --------------------------------------------
    @contextlib.contextmanager
    def tracking(self):
        """Scope within which issued AckKeys are tracked for THREAD/GLOBAL
        fences.  Channel ops call :meth:`track`; ``fence`` drains."""
        prev, self._trace.outstanding = self._trace.outstanding, []
        self._trace.active = True
        try:
            yield self
        finally:
            self._trace.outstanding = prev
            self._trace.active = prev is not None and bool(prev)

    @contextlib.contextmanager
    def no_tracking(self):
        """Suspend outstanding-op tracking.

        Required inside ``lax.while_loop``/``scan`` bodies: tokens created
        there are loop-local tracers and must not escape into the trace-level
        outstanding list (ordering inside the loop is already carried by the
        loop state's data dependencies)."""
        prev = getattr(self._trace, "paused", False)
        self._trace.paused = True
        try:
            yield
        finally:
            self._trace.paused = prev

    def track(self, ack: AckKey) -> AckKey:
        if getattr(self._trace, "paused", False):
            return ack
        self._trace.outstanding.append(ack)
        return ack

    def outstanding(self) -> AckKey:
        acc = AckKey.empty()
        for a in self._trace.outstanding:
            acc = acc | a
        return acc

    # -- fences (paper §5.3) -------------------------------------------------
    def fence(self, *args, scope: FenceScope = FenceScope.GLOBAL,
              peer: int | None = None):
        """Order ``args`` after outstanding ops per ``scope``.

        GLOBAL: joins every outstanding op and drains the tracking list.
        THREAD: joins every outstanding op issued in this trace (in SPMD one
                trace == one thread; kept as a distinct scope because the
                descriptor filter differs on a multi-controller backend).
        PAIR:   joins only ops targeting ``peer``; other ops stay outstanding
                so the scheduler may still overlap them (the cheap fence).
        """
        self.fence_counts[scope] += 1
        out_ack = self.outstanding()
        if scope == FenceScope.GLOBAL:
            self._trace.outstanding = []
            return join(out_ack, *args, scope=FenceScope.GLOBAL)
        if scope == FenceScope.THREAD:
            self._trace.outstanding = []
            return join(out_ack, *args, scope=FenceScope.GLOBAL)
        # PAIR: keep non-matching ops outstanding
        kept_tokens, kept_descs = [], []
        for tok, d in zip(out_ack.tokens, out_ack.descs):
            if not (d.peers == ALL_PEERS or (peer is not None and peer in d.peers)):
                kept_tokens.append(tok)
                kept_descs.append(d)
        self._trace.outstanding = [AckKey(kept_tokens, kept_descs)]
        return join(out_ack, *args, peer=peer, scope=FenceScope.PAIR)


def make_manager(num_participants: int, axis: str = "nodes",
                 mesh: Optional[jax.sharding.Mesh] = None,
                 backend=None) -> Manager:
    return Manager(Runtime(num_participants, axis=axis, mesh=mesh),
                   backend=backend)

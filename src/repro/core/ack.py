"""Completion tracking and fences — LOCO's memory-consistency mechanism.

Paper mapping (LOCO §5.2-§5.3):

* ``AckKey`` is the completion handle returned by every asynchronous channel
  operation.  In LOCO it is a lock-free bitset cleared by the polling thread;
  in the SPMD/XLA adaptation it is a pytree of *dependency tokens* — small
  arrays that are data-dependent on the issued operation — plus a static
  tuple of :class:`OpDesc` descriptors (LOCO's "internal tracking mechanism"
  of outstanding operations).

* ``fence`` induces the synchronizes-with edge.  On RDMA, LOCO ranges from
  waiting on an ack_key (pair-only) to a zero-length read to every peer
  (global).  Under XLA, program order is *not* execution order: the scheduler
  freely reorders and overlaps collectives.  The honest analogue of a LOCO
  fence is therefore ``lax.optimization_barrier`` joining exactly the tokens
  in scope — prior ops must be scheduled before anything data-dependent on
  the fence output.  The *scope* (PAIR < THREAD < GLOBAL) selects how many
  tokens are joined, i.e. how much freedom the scheduler keeps.  This is the
  same performance knob the paper exposes, realized TPU-natively.

Like LOCO, the fence implementation inspects the tracked outstanding
operations and joins only what the requested scope requires ("LOCO ...
dynamically chooses the best performing implementation").
"""
from __future__ import annotations

import enum
from typing import Any, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp


class FenceScope(enum.IntEnum):
    """Fence scopes, weakest to strongest (paper §5.3)."""

    PAIR = 0    # order ops targeting one given peer
    THREAD = 1  # order all ops issued by the calling participant trace
    GLOBAL = 2  # order all outstanding ops tracked by the manager


# Peer wildcard used by broadcast-style operations.
ALL_PEERS: Tuple = ("all",)


class OpDesc(NamedTuple):
    """Static descriptor of one issued remote operation.

    kind:    'write' | 'read' | 'atomic' | 'bcast' | 'barrier'
    channel: full channel name that issued the op (e.g. "kv/locks/3")
    peers:   tuple of target participant ids, or ALL_PEERS
    nbytes:  payload bytes moved per participant (for the roofline ledger)
    """

    kind: str
    channel: str
    peers: Tuple
    nbytes: int


@jax.tree_util.register_pytree_node_class
class AckKey:
    """Completion handle for asynchronous channel operations (paper §5.2).

    AckKeys are unioned with ``|`` so a higher-level operation (e.g. an SST
    broadcast) builds its key from its component operations (the paper's
    example verbatim).
    """

    def __init__(self, tokens: Sequence[Any] = (), descs: Sequence[OpDesc] = ()):
        self.tokens = list(tokens)
        self.descs = tuple(descs)

    # -- composition -------------------------------------------------------
    def union(self, other: "AckKey") -> "AckKey":
        return AckKey(self.tokens + other.tokens, self.descs + other.descs)

    __or__ = union

    @staticmethod
    def empty() -> "AckKey":
        return AckKey()

    # -- completion --------------------------------------------------------
    def query(self) -> jax.Array:
        """True once the tracked operations are complete.

        In the lockstep SPMD execution model a collective's results are
        available exactly when it completes, so ``query`` returns a True
        that is *data-dependent* on every tracked op — consuming it orders
        the consumer after the ops, which is the strongest statement the
        XLA execution model permits.
        """
        flag = jnp.asarray(True)
        if self.tokens:
            out = jax.lax.optimization_barrier(tuple(self.tokens) + (flag,))
            flag = out[-1]
        return flag

    def wait(self) -> jax.Array:
        """Blocking wait == consuming the completion flag in SPMD."""
        return self.query()

    # -- introspection (used by Manager.fence to pick minimal scope) -------
    def tokens_for_peer(self, peer: int):
        toks = []
        for tok, d in zip(self.tokens, self.descs):
            if d.peers == ALL_PEERS or peer in d.peers:
                toks.append(tok)
        return toks

    @property
    def nbytes(self) -> int:
        return sum(d.nbytes for d in self.descs)

    # -- pytree ------------------------------------------------------------
    def tree_flatten(self):
        return tuple(self.tokens), self.descs

    @classmethod
    def tree_unflatten(cls, descs, tokens):
        return cls(list(tokens), descs)

    def __repr__(self):
        return f"AckKey({len(self.tokens)} ops, {self.nbytes}B)"


def make_ack(token: Any, kind: str, channel: str, peers: Tuple, nbytes: int) -> AckKey:
    """Build a single-op AckKey whose token is ``token`` (any array pytree)."""
    return AckKey([token], [OpDesc(kind, channel, peers, int(nbytes))])


def join(ack: AckKey, *args, peer: int | None = None,
         scope: FenceScope = FenceScope.GLOBAL):
    """Order ``args`` after the operations tracked by ``ack``.

    Returns ``args`` (single value if one arg) such that any computation
    consuming them is scheduled after the in-scope tracked ops.  PAIR scope
    joins only tokens whose op targets ``peer``.
    """
    if scope == FenceScope.PAIR and peer is not None:
        toks = ack.tokens_for_peer(peer)
    else:
        toks = ack.tokens
    if not toks:
        return args[0] if len(args) == 1 else args
    flat_args, treedef = jax.tree.flatten(args)
    out = jax.lax.optimization_barrier(tuple(toks) + tuple(flat_args))
    new_args = jax.tree.unflatten(treedef, out[len(toks):])
    return new_args[0] if len(new_args) == 1 else new_args

"""Pallas remote-DMA kernels for the colls verb layer (DESIGN.md §15).

The ``pallas`` backend lowers the batched one-sided verbs onto explicit
DMA-style kernels instead of plain jnp gathers: a requester builds fixed
width transfer *descriptors* (the NIC work-queue-entry analogue), the
home node serves/commits the described rows with a Pallas kernel, and
every kernel **counts the bytes it actually moves** from the same masks
that drive the copies.  Those measured counters are what
``benchmarks/bench_roofline.py`` pins the TrafficLedger's *modeled* cost
contract against — the ledger stops being a vibe the moment the two can
drift.

Dispatch follows :mod:`repro.kernels.ops`: Pallas on TPU, interpret mode
on CPU (the validation substrate — the kernel body runs with identical
semantics), ``force_ref=True`` routes to the pure-jnp oracle used by the
A/B tests.  The *wire hop* between the requester-side and home-side
kernels is an XLA collective on every substrate (all-gather of
descriptors, psum_scatter of served rows), exactly as in
:func:`repro.core.colls._serve_scatter`; the kernels are the two ends of
that hop.

On the chip the home table stays in HBM and never enters VMEM whole:
the row kernels move only the row tiles that hold described rows (see
"row tiles" below), the scatter updates the table in place, indices and
masks ride in SMEM as scalar prefetch, and the byte counters are SMEM
scalars.  Under ``vmap`` (the single-device participant binding) Pallas
runs a scalar-prefetch kernel once per participant, so each call still
sees one participant's table.

All kernels take 2-D ``(rows, width)`` buffers — callers flatten item
dims — and are dtype-generic.  Descriptor layout (8 × int32 =
:data:`DESC_BYTES` bytes, the explicit constant the backend's cost model
cites):

    word 0  op        1 = read, 2 = write
    word 1  target    home participant id
    word 2  index     row within the home's buffer
    word 3  enabled   lane rides the wire iff != 0
    word 4  length    row payload bytes
    word 5  seq       lane sequence number (application order)
    word 6-7          reserved (zero)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: int32 words per transfer descriptor.
DESC_WORDS = 8
#: Bytes of one remote-DMA descriptor on the wire — the work-queue-entry
#: header every described lane pays (the backends.AM_HDR_BYTES idiom).
DESC_BYTES = DESC_WORDS * 4

OP_READ = 1
OP_WRITE = 2


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# descriptor build (requester side)
# ---------------------------------------------------------------------------

def _build_desc_kernel(req_ref, out_ref, nb_ref, *, op, row_nbytes):
    # req columns: target | index | enabled | wire — one lane per row, so
    # every descriptor word is a lane-broadcast select, no scalar stores
    req = req_ref[...]
    shape = out_ref.shape
    word = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    seq = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    desc = jnp.where(word == 0, jnp.int32(op), jnp.int32(0))
    desc = jnp.where(word == 1, req[:, 0:1], desc)
    desc = jnp.where(word == 2, req[:, 1:2], desc)
    desc = jnp.where(word == 3, (req[:, 2:3] != 0).astype(jnp.int32), desc)
    desc = jnp.where(word == 4, jnp.int32(row_nbytes), desc)
    desc = jnp.where(word == 5, seq, desc)
    out_ref[...] = desc
    nb_ref[0, 0] = jnp.sum((req[:, 3:4] != 0).astype(jnp.int32)) \
        * jnp.int32(DESC_BYTES)


def _build_desc_ref(targets, indices, en, wire, op, row_nbytes):
    R = targets.shape[0]
    desc = jnp.zeros((R, DESC_WORDS), jnp.int32)
    desc = desc.at[:, 0].set(jnp.int32(op))
    desc = desc.at[:, 1].set(targets)
    desc = desc.at[:, 2].set(indices)
    desc = desc.at[:, 3].set((en != 0).astype(jnp.int32))
    desc = desc.at[:, 4].set(jnp.int32(row_nbytes))
    desc = desc.at[:, 5].set(jnp.arange(R, dtype=jnp.int32))
    return desc, jnp.sum((wire != 0).astype(jnp.int32)) \
        * jnp.int32(DESC_BYTES)


def build_descriptors(targets, indices, en, *, wire=None, op=OP_READ,
                      row_nbytes=0, force_ref=False):
    """Build the (R, :data:`DESC_WORDS`) int32 descriptor block for R
    request lanes plus the measured descriptor wire bytes
    (:data:`DESC_BYTES` per ``wire`` lane; ``wire`` defaults to ``en``).
    The two masks split for writes, where self-targeted lanes stay
    *enabled* — the home applies them — but move no descriptor over the
    wire.  The descriptor tensor is what actually rides the request
    gather — colls reads target/index/enabled back out of words 1–3."""
    targets = targets.astype(jnp.int32)
    indices = indices.astype(jnp.int32)
    en = jnp.asarray(en).astype(jnp.int32)
    wire = en if wire is None else jnp.asarray(wire).astype(jnp.int32)
    if force_ref:
        return _build_desc_ref(targets, indices, en, wire, op, row_nbytes)
    R = targets.shape[0]
    kern = functools.partial(_build_desc_kernel, op=int(op),
                             row_nbytes=int(row_nbytes))
    desc, nb = pl.pallas_call(
        kern,
        out_shape=(jax.ShapeDtypeStruct((R, DESC_WORDS), jnp.int32),
                   jax.ShapeDtypeStruct((1, 1), jnp.int32)),
        out_specs=(pl.BlockSpec(memory_space=pltpu.VMEM),
                   pl.BlockSpec(memory_space=pltpu.SMEM)),
        interpret=_interpret(),
    )(jnp.stack([targets, indices, en, wire], axis=1))
    return desc, nb[0, 0]


# ---------------------------------------------------------------------------
# row tiles
# ---------------------------------------------------------------------------
#
# A 2-D table in HBM is laid out in (8, 128) tiles of 32-bit words, and
# Mosaic slices such a table only in whole tiles — a kvstore row of
# ``value_width + 3`` words is not even a whole lane tile.  So the row
# kernels run a grid over lanes and let the Pallas pipeline move one
# (T, width) row tile per lane, chosen by the scalar-prefetched index;
# inside the tile the kernel picks or patches the described row with a
# sublane select.  Rows travel as same-width signed integers, which keeps
# the select exact for every dtype (float bit patterns included).

def _tile_rows(dtype) -> int:
    return 8 * 4 // jnp.dtype(dtype).itemsize


def _as_int_rows(buf2d, T):
    """Bit-identical integer view of ``buf2d``, padded with zero rows to a
    whole number of T-row tiles (a no-op for aligned tables)."""
    bits = jax.lax.bitcast_convert_type(
        buf2d, jnp.dtype(f"int{8 * buf2d.dtype.itemsize}"))
    return jnp.pad(bits, ((0, -buf2d.shape[0] % T), (0, 0)))


def _from_int_rows(bits, n_rows, dtype):
    return jax.lax.bitcast_convert_type(bits[:n_rows], dtype)


def _pick_row(tile, sub):
    """Row ``sub`` of a (T, width) integer tile as a (1, width) value:
    exactly one term of the sum is non-zero, so it is bitwise the row."""
    row_id = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 0)
    return jnp.sum(jnp.where(row_id == sub, tile, jnp.zeros_like(tile)),
                   axis=0, keepdims=True, dtype=tile.dtype)


#: lanes are served in grid order, one step after another
_SEQUENTIAL = pltpu.CompilerParams(dimension_semantics=("arbitrary",))


# ---------------------------------------------------------------------------
# row serve (home side, reads)
# ---------------------------------------------------------------------------

def _gather_kernel(idx_ref, mask_ref, tile_ref, out_ref, nb_ref, *,
                   row_nbytes):
    i = pl.program_id(0)
    T = out_ref.shape[0]
    served = mask_ref[i] != 0

    @pl.when(i == 0)
    def _():
        nb_ref[0, 0] = jnp.int32(0)

    # lanes i // T * T ... + T - 1 share one output tile
    @pl.when(i % T == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    row = _pick_row(tile_ref[...], idx_ref[i] % T)
    row_id = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 0)
    out_ref[...] = jnp.where((row_id == i % T) & served, row, out_ref[...])
    nb_ref[0, 0] += jnp.where(served, jnp.int32(row_nbytes), jnp.int32(0))


def _gather_ref(buf2d, indices, mask, row_nbytes):
    rows = buf2d[indices]
    m = (mask != 0)
    rows = jnp.where(m[:, None], rows, jnp.zeros_like(rows))
    return rows, jnp.sum(m.astype(jnp.int32)) * jnp.int32(row_nbytes)


def gather_rows(buf2d, indices, mask, *, force_ref=False):
    """Serve N described rows from the home buffer: lane i receives
    ``buf2d[indices[i]]`` iff ``mask[i]`` (zeros otherwise), plus the
    measured payload bytes — one row width per served lane, counted from
    the same mask that drives the copy.  ``buf2d``: (slots, width);
    ``indices`` must be pre-clipped to range."""
    indices = indices.astype(jnp.int32)
    mask = jnp.asarray(mask).astype(jnp.int32)
    row_nbytes = int(buf2d.shape[1]) * buf2d.dtype.itemsize
    if force_ref:
        return _gather_ref(buf2d, indices, mask, row_nbytes)
    N, W = indices.shape[0], buf2d.shape[1]
    T = _tile_rows(buf2d.dtype)
    table = _as_int_rows(buf2d, T)
    n_pad = N + -N % T
    kern = functools.partial(_gather_kernel, row_nbytes=row_nbytes)
    rows, nb = pl.pallas_call(
        kern,
        out_shape=(jax.ShapeDtypeStruct((n_pad, W), table.dtype),
                   jax.ShapeDtypeStruct((1, 1), jnp.int32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(N,),
            in_specs=[pl.BlockSpec((T, W),
                                   lambda i, idx, msk: (idx[i] // T, 0))],
            out_specs=(pl.BlockSpec((T, W), lambda i, idx, msk: (i // T, 0)),
                       pl.BlockSpec((1, 1), lambda i, idx, msk: (0, 0),
                                    memory_space=pltpu.SMEM))),
        compiler_params=_SEQUENTIAL,
        interpret=_interpret(),
    )(indices, mask, table)
    return _from_int_rows(rows, N, buf2d.dtype), nb[0, 0]


# ---------------------------------------------------------------------------
# row commit (home side, writes)
# ---------------------------------------------------------------------------

def _scatter_kernel(lane_ref, tile_of_ref, sub_ref, apply_ref, wire_ref,
                    val_ref, tile_ref, out_ref, nb_ref, *, row_nbytes):
    # step j serves lane lane_ref[j]; steps are ordered by home tile and,
    # within a tile, by lane — so each tile is fetched once, patched by
    # its lanes in lane order (a later lane to the same row wins), and
    # written back once.
    j = pl.program_id(0)
    T = out_ref.shape[0]
    lane = lane_ref[j]

    @pl.when(j == 0)
    def _():
        nb_ref[0, 0] = jnp.int32(0)

    @pl.when((j == 0) | (tile_of_ref[jnp.maximum(j - 1, 0)]
                         != tile_of_ref[j]))
    def _():
        out_ref[...] = tile_ref[...]

    @pl.when(apply_ref[lane] != 0)
    def _():
        row_id = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 0)
        out_ref[...] = jnp.where(row_id == sub_ref[j],
                                 _pick_row(val_ref[...], lane % T),
                                 out_ref[...])

    nb_ref[0, 0] += jnp.where(wire_ref[lane] != 0, jnp.int32(row_nbytes),
                              jnp.int32(0))


def _scatter_ref(buf2d, indices, values, apply_mask, wire_mask, row_nbytes):
    n = indices.shape[0]
    # sequential in-order application == last-writer-wins, computed as a
    # winner mask so one scatter commits the surviving rows (the oracle
    # mirror of the kernel's lane-order application).
    win = apply_mask != 0
    order = jnp.arange(n)
    later_same = (indices[None, :] == indices[:, None]) & win[None, :] \
        & (order[None, :] > order[:, None])
    win = win & ~jnp.any(later_same, axis=1)
    row = jnp.where(win, indices, buf2d.shape[0])
    out = buf2d.at[row].set(values, mode="drop")
    return out, jnp.sum((wire_mask != 0).astype(jnp.int32)) \
        * jnp.int32(row_nbytes)


def scatter_rows(buf2d, indices, values, apply_mask, wire_mask, *,
                 force_ref=False):
    """Commit N described rows into the home buffer **in lane order** —
    the kernel applies lanes sequentially, realizing last-writer-wins
    natively, so racy lanes need no winner-mask precomputation.  Lane i
    stores ``values[i]`` at ``indices[i]`` iff ``apply_mask[i]``;
    measured payload bytes count ``wire_mask`` lanes (the caller excludes
    self-origin lanes — a local store moves no wire bytes but still
    commits).  ``indices`` must be pre-clipped to range.  The table is
    updated in place.  Returns (new_buf2d, measured_bytes)."""
    indices = indices.astype(jnp.int32)
    apply_mask = jnp.asarray(apply_mask).astype(jnp.int32)
    wire_mask = jnp.asarray(wire_mask).astype(jnp.int32)
    row_nbytes = int(buf2d.shape[1]) * buf2d.dtype.itemsize
    if force_ref:
        return _scatter_ref(buf2d, indices, values, apply_mask, wire_mask,
                            row_nbytes)
    n, W = indices.shape[0], buf2d.shape[1]
    T = _tile_rows(buf2d.dtype)
    table = _as_int_rows(buf2d, T)
    vals = _as_int_rows(values.astype(buf2d.dtype), T)
    # visit lanes grouped by home tile, keeping lane order inside a tile
    lane = jnp.argsort(indices // T, stable=True).astype(jnp.int32)
    tile_of = indices[lane] // T
    sub = indices[lane] % T
    kern = functools.partial(_scatter_kernel, row_nbytes=row_nbytes)
    out, nb = pl.pallas_call(
        kern,
        out_shape=(jax.ShapeDtypeStruct(table.shape, table.dtype),
                   jax.ShapeDtypeStruct((1, 1), jnp.int32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(n,),
            in_specs=[pl.BlockSpec((T, W),
                                   lambda j, ln, tl, *_: (ln[j] // T, 0)),
                      pl.BlockSpec((T, W),
                                   lambda j, ln, tl, *_: (tl[j], 0))],
            out_specs=(pl.BlockSpec((T, W),
                                    lambda j, ln, tl, *_: (tl[j], 0)),
                       pl.BlockSpec((1, 1), lambda j, *_: (0, 0),
                                    memory_space=pltpu.SMEM))),
        input_output_aliases={6: 0},
        compiler_params=_SEQUENTIAL,
        interpret=_interpret(),
    )(lane, tile_of, sub, apply_mask, wire_mask, vals, table)
    return _from_int_rows(out, buf2d.shape[0], buf2d.dtype), nb[0, 0]

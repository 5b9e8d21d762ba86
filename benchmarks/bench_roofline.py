"""§Roofline summary + §15 ledger-agreement validation.

Three sections, all landing in the CSV:

1. **Dry-run roofline table** — reads ``reports/dryrun/*.json`` into the
   per-cell table (one row per arch × shape; us_per_call = bound term in
   µs).  Unchanged from the original bench.

2. **DMA agreement (kvstore hot paths)** — drives the §5 kvstore GET and
   UPDATE windows through the ``pallas`` backend with the ledger enabled
   and asserts, per verb, that the bytes the remote-DMA kernels *measure*
   (descriptors emitted + rows served/committed, counted from the masks
   that drive the copies) agree with the *modeled* (desc+row)·lane
   contract within :data:`DMA_AGREEMENT_RTOL`.  Ledger drift on the
   channel hot paths is a bench failure, not a vibe.

3. **HLO probe (closed form)** — compiles a saturated read/write
   microbench under ``shard_map`` on 8 forced host devices (subprocess —
   XLA device-count flags must be set before jax imports) and checks the
   compiled HLO's collective bytes against the ledger's modeled bytes via
   the closed form ``hlo = (P-1)/P · modeled``: with every lane remote
   and unique, the descriptor all-gather ships (P-1)·R·DESC bytes per
   device and the serve/commit hop (P-1)·R·|row| — exactly (P-1)/P of
   the P·R·(DESC+|row|) the ledger models.  This ties the model to what
   XLA actually puts on the wire, independent of the kernel counters.
"""
from __future__ import annotations

import glob
import json
import os
import subprocess
import sys

import numpy as np

from .common import Csv

# §15 pinned tolerances: the kernel-counter tier agrees with the model
# exactly by construction (same masks), so 1% catches any drift; the HLO
# tier crosses the XLA scheduler, so it gets a conventional 5%.
DMA_AGREEMENT_RTOL = 0.01
HLO_PROBE_RTOL = 0.05


def _dryrun_rows(csv: Csv, report_dir: str):
    if os.path.isdir("reports/final") and glob.glob("reports/final/*.json"):
        report_dir = "reports/final"   # optimized-framework re-measurement
    files = sorted(glob.glob(os.path.join(report_dir, "*__single*.json")))
    if not files:
        csv.add("roofline_missing", 0.0,
                "run repro.launch.dryrun first")
        return
    for f in files:
        with open(f) as fh:
            d = json.load(fh)
        if d.get("skipped"):
            csv.add(f"roofline_{d['arch']}_{d['shape']}", 0.0,
                    f"skipped={d['skipped'][:40]}")
            continue
        if "compute_s" not in d:
            continue
        bound = max(d["compute_s"], d["memory_s"], d["collective_s"])
        csv.add(
            f"roofline_{d['arch']}_{d['shape']}",
            bound * 1e6,
            f"dominant={d['dominant']};frac={d['roofline_fraction']:.3f};"
            f"compute_ms={d['compute_s'] * 1e3:.1f};"
            f"memory_ms={d['memory_s'] * 1e3:.1f};"
            f"collective_ms={d['collective_s'] * 1e3:.1f};"
            f"fits16g={d.get('fits_16g_hbm')}")


def _dma_agreement(csv: Csv, smoke: bool):
    """Measured-vs-modeled bytes on the kvstore GET/UPDATE hot paths."""
    import jax
    import jax.numpy as jnp

    from repro.core import GET, INSERT, NOP, UPDATE, KVStore, make_manager

    P, B, vw, keyspace = 4, 8, 4, 32
    mgr = make_manager(P, backend="pallas")
    mgr.traffic.enable()
    kv = KVStore(None, "roofkv", mgr, slots_per_node=keyspace,
                 value_width=vw, num_locks=32, index_capacity=4 * keyspace,
                 placement="hashed")
    step = jax.jit(lambda s, o, k, v: mgr.runtime.run(
        kv.op_window, s, o, k, v))
    st = kv.init_state()
    keys = np.arange(1, keyspace + 1, dtype=np.uint32)
    for lo in range(0, keyspace, P * B):
        chunk = keys[lo:lo + P * B]
        op = np.full((P * B,), NOP, np.int32)
        kk = np.ones((P * B,), np.uint32)
        op[:len(chunk)] = INSERT
        kk[:len(chunk)] = chunk
        vv = np.repeat(kk.astype(np.int32)[:, None], vw, axis=1)
        st, _ = step(st, jnp.asarray(op.reshape(P, B)),
                     jnp.asarray(kk.reshape(P, B)),
                     jnp.asarray(vv.reshape(P, B, vw)))
    jax.block_until_ready(st)
    jax.effects_barrier()
    mgr.traffic.reset()
    # GET hot path (read_batch tier) then UPDATE hot path (write_batch
    # tier), duplicate keys included so coalescing/collisions are live.
    rng = np.random.default_rng(7)
    for _ in range(1 if smoke else 4):
        for opcode in (GET, UPDATE):
            kk = rng.integers(1, keyspace + 1, size=P * B).astype(np.uint32)
            op = np.full((P * B,), opcode, np.int32)
            vv = np.repeat(kk.astype(np.int32)[:, None] * 5 + 2, vw, axis=1)
            st, _ = step(st, jnp.asarray(op.reshape(P, B)),
                         jnp.asarray(kk.reshape(P, B)),
                         jnp.asarray(vv.reshape(P, B, vw)))
    jax.block_until_ready(st)
    jax.effects_barrier()
    modeled = mgr.traffic.summary()
    measured = mgr.traffic.dma_summary()
    assert measured, "pallas backend recorded no measured DMA tier"
    suffixes = set()
    for verb, got in sorted(measured.items()):
        want = modeled.get(verb, {"bytes": 0.0})["bytes"]
        rel = abs(got["bytes"] - want) / max(want, 1.0)
        assert rel <= DMA_AGREEMENT_RTOL, \
            (f"ledger drift on {verb}: measured={got['bytes']:.0f} "
             f"modeled={want:.0f} rel={rel:.4f} > {DMA_AGREEMENT_RTOL}")
        csv.add(f"roofline_dma_{verb}", 0.0,
                f"measured={got['bytes']:.0f};modeled={want:.0f};"
                f"rel={rel:.5f};calls={got['calls']:.0f}")
        if verb.endswith(("get_batch", "read_batch")):
            suffixes.add("read")
        if verb.endswith("write_batch"):
            suffixes.add("write")
    # the hot paths themselves must have been exercised and checked
    assert "read" in suffixes, sorted(measured)
    assert "write" in suffixes, sorted(measured)


_PROBE_SRC = r"""
import json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as PS
from repro.core.backends import PallasDmaBackend
from repro.core.runtime import TrafficLedger
from repro.roofline.analysis import collective_bytes

P, S, W, R = 8, 16, 5, 4
mesh = jax.make_mesh((P,), ("nodes",))
bk = PallasDmaBackend()
out = {}
for opname in ("read", "write"):
    led = TrafficLedger()
    led.enable()

    def prog(buf, tg, ix, vv, _op=opname, _led=led):
        if _op == "read":
            return bk.read_batch(buf, tg, ix, "nodes", ledger=_led,
                                 verb="probe"), buf
        return jnp.zeros((R, W), jnp.int32), bk.write_batch(
            buf, tg, ix, vv, "nodes", ledger=_led, verb="probe")

    def f(b, t, i, v):
        sq = lambda x: jnp.squeeze(x, 0)
        r, nb = prog(sq(b), sq(t), sq(i), sq(v))
        return jnp.expand_dims(r, 0), jnp.expand_dims(nb, 0)

    sm = jax.shard_map(f, mesh=mesh, in_specs=PS("nodes"),
                       out_specs=PS("nodes"), check_vma=False)
    rng = np.random.default_rng(0)
    buf = jnp.asarray(rng.integers(0, 99, (P, S, W)).astype(np.int32))
    # saturated + unique: every lane remote (next neighbour), distinct rows
    tg = jnp.broadcast_to(((jnp.arange(P) + 1) % P)[:, None].astype(
        jnp.int32), (P, R))
    ix = jnp.broadcast_to(jnp.arange(R, dtype=jnp.int32), (P, R))
    vv = jnp.asarray(rng.integers(0, 99, (P, R, W)).astype(np.int32))
    jf = jax.jit(sm)
    hlo = jf.lower(buf, tg, ix, vv).compile().as_text()
    res = jf(buf, tg, ix, vv)
    jax.block_until_ready(res)
    jax.effects_barrier()
    cb = collective_bytes(hlo, P)
    out[opname] = {"hlo_bytes": cb["total_bytes"],
                   "per_op": cb["per_op_bytes"],
                   "modeled": led.total_bytes(),
                   "measured": led.total_dma_bytes()}
print(json.dumps(out))
"""


def _hlo_probe(csv: Csv):
    """Closed-form HLO check: compiled collective bytes == (P-1)/P of the
    modeled bytes on a saturated unique-lane read/write microbench."""
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    # the probe runs on 8 fake host devices; the parent may already hold
    # the accelerator, which a second process cannot open
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "-c", _PROBE_SRC], env=env,
                          capture_output=True, text=True, timeout=560)
    assert proc.returncode == 0, \
        f"HLO probe subprocess failed:\n{proc.stdout}\n{proc.stderr}"
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    P = 8
    for opname, d in sorted(out.items()):
        want = d["modeled"] * (P - 1) / P
        rel = abs(d["hlo_bytes"] - want) / max(want, 1.0)
        assert rel <= HLO_PROBE_RTOL, \
            (f"HLO/{opname}: compiled wire bytes {d['hlo_bytes']:.0f} vs "
             f"(P-1)/P·modeled {want:.0f} rel={rel:.4f} "
             f"(per_op={d['per_op']})")
        # the kernel-counter tier rides along: it must agree with the
        # model here too (saturated cell — exact by construction)
        assert abs(d["measured"] - d["modeled"]) \
            <= DMA_AGREEMENT_RTOL * d["modeled"], d
        csv.add(f"roofline_hlo_{opname}", 0.0,
                f"hlo={d['hlo_bytes']:.0f};modeled={d['modeled']:.0f};"
                f"closed_form={want:.0f};rel={rel:.5f}")


def run(csv: Csv, report_dir: str = "reports/dryrun", smoke: bool = False):
    _dryrun_rows(csv, report_dir)
    _dma_agreement(csv, smoke)
    _hlo_probe(csv)

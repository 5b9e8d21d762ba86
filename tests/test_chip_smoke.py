"""``chip_smoke.py`` rehearsed on the CPU at a tiny size.

The smoke itself refuses to run off the chip; these tests drive its
phases directly, with the store shrunk, so its oracle, its bitwise
comparisons and its four-chip path are exercised on every run of the
suite.
"""
import importlib.util
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import GET, UPDATE
from repro.launch import smoke

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


def _load_chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def tiny(monkeypatch):
    monkeypatch.setattr(smoke, "ONE_CHIP_SLOTS", 64)
    monkeypatch.setattr(smoke, "FOUR_CHIP_SLOTS", 128)
    monkeypatch.setattr(smoke, "WINDOW", 4)


def test_record_values_match_on_host_and_device():
    keys = np.array([[1, 2], [3, 0xFFFFFFFF]], np.uint32)
    versions = np.array([[0, 7], [1 << 20, 3]], np.uint32)
    host = smoke.record_values(np, keys, versions, seed=5, width=9)
    dev = smoke.record_values(jnp, keys, versions, seed=5, width=9)
    assert host.shape == (2, 2, 9) and host.dtype == np.int32
    np.testing.assert_array_equal(host, np.asarray(dev))
    assert len(np.unique(host.reshape(-1, 9), axis=0)) == 4


def test_oracle_follows_linearization_order_and_catches_a_stale_get():
    oracle = smoke.Oracle(4)
    oracle.version[1:] = 0
    ops = np.array([[GET, UPDATE], [UPDATE, GET]], np.int32)
    keys = np.array([[2, 2], [2, 2]], np.uint32)
    versions = np.array([[0, 11], [12, 0]], np.uint32)
    found = np.ones((2, 2), bool)
    values = np.zeros((2, 2, 3), np.int32)
    # GETs linearize at the window start: both read version 0
    values[0, 0] = values[1, 1] = smoke.record_values(np, 2, 0, 1, 3)
    assert oracle.check_window(ops, keys, versions, found, values, 1) == 2
    # the later lane in (participant, lane) order wins
    assert oracle.version[2] == 12
    stale = np.zeros((1, 3), np.int32)
    stale[0] = smoke.record_values(np, 2, 11, 1, 3)
    with pytest.raises(AssertionError, match="value mismatch"):
        oracle.check_gets(np.array([2], np.uint32), np.ones(1, bool),
                          stale, 1)


def test_one_chip_phases_rehearse_on_cpu(tiny, monkeypatch, capsys):
    """Load, oracle check and the pallas phase's bitwise comparison; on
    the CPU the kernels run interpreted, so the HLO holds no
    ``tpu_custom_call`` and only that one check is waived."""
    chip_smoke = _load_chip_smoke()
    require = smoke.require

    def waive_kernel_check(ok, msg):
        if "no compiled Pallas kernel" not in msg:
            require(ok, msg)
    monkeypatch.setattr(smoke, "require", waive_kernel_check)
    chip_smoke.one_chip(seed=3)
    out = capsys.readouterr().out
    assert "[onesided] oracle:" in out
    assert "[pallas] results and final state bitwise-equal" in out


def test_four_chip_phase_rehearses_on_virtual_devices():
    prog = textwrap.dedent(f"""
        import os, sys
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        sys.path.insert(0, {ROOT!r})
        import chip_smoke
        from repro.launch import smoke
        smoke.FOUR_CHIP_SLOTS, smoke.WINDOW = 128, 4
        chip_smoke.four_chip(seed=1)
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    r = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                       text=True, env=env, timeout=600)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "bitwise-equal to the one-device vmap run" in r.stdout
    assert r.stdout.count("state bytes of") == 4


def test_smoke_refuses_a_cpu_device():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, SMOKE], capture_output=True,
                       text=True, env=env, cwd=ROOT, timeout=300)
    assert r.returncode != 0
    assert "'cpu'" in r.stderr
    assert '"ok"' not in r.stdout

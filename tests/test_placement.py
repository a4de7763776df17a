"""Placement in a policy's preset name (``"FROID+data4"``) and the served
path on a mesh: every ``execute_many`` wave runs on the whole mesh, padded
to a multiple of its data axis where the axis does not divide it."""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.core import FROID, HEKATON, ROUTED, resolve_policy
from repro.core.policy import data_mesh

CHILD = Path(__file__).with_name("placement_x4_child.py")


@pytest.mark.parametrize("name", ["FROID+data1", "froid+data1",
                                  "Froid+data1"])
def test_placement_parses(name):
    pol = resolve_policy(name)
    assert pol == FROID and pol.fingerprint() == FROID.fingerprint()
    assert pol.shard_batches
    assert dict(pol.mesh.shape) == {"data": 1}
    assert list(pol.mesh.devices.flat) == jax.local_devices()[:1]
    assert pol.shard_devices() == 1


def test_placement_keeps_the_preset():
    pol = resolve_policy("hekaton+data1")
    assert pol == HEKATON and pol.mesh is not None
    assert resolve_policy("routed+data1").route


def test_placement_errors_on_too_few_devices():
    n = len(jax.local_devices()) + 1
    with pytest.raises(ValueError, match=f"data{n} needs {n} local devices"):
        resolve_policy(f"FROID+data{n}")
    with pytest.raises(ValueError, match="needs"):
        data_mesh(n)


@pytest.mark.parametrize("name,want", [
    ("froid", FROID), ("FROID", FROID), ("hekaton", HEKATON),
    ("routed", ROUTED)])
def test_plain_names_unchanged(name, want):
    pol = resolve_policy(name)
    assert pol is want and pol.mesh is None and pol.shard_devices() == 1


@pytest.mark.parametrize("name", ["FROID+data", "FROID+data0", "FROID+",
                                  "nope+data1", "FROID+model4", "+data4"])
def test_malformed_placements_are_unknown_presets(name):
    with pytest.raises(KeyError, match="unknown policy preset"):
        resolve_policy(name)


@pytest.fixture(scope="module")
def on_four_devices():
    """The child's findings, from a process of its own on four virtual
    CPU devices (this process has JAX on one)."""
    root = Path(__file__).resolve().parents[1]
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": (os.environ.get("XLA_FLAGS", "") + " --xla_force_"
                         "host_platform_device_count=4").strip(),
           "PYTHONPATH": os.pathsep.join([str(root / "src"), str(root)])}
    p = subprocess.run([sys.executable, str(CHILD)], env=env, cwd=root,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_padded_and_dividing_waves_equal_the_serial_loop(on_four_devices):
    out = on_four_devices
    assert out["devices"] == 4 and out["shard_devices"] == 4
    assert len(out["waves"]) == 12
    for key, w in out["waves"].items():
        k = int(key.split(":")[1])
        assert w["same"], key
        assert w["sharded"], key
        assert w["bucket"] % 4 == 0 and w["bucket"] >= k, key
        assert w["pad"] == w["bucket"] - k, key
    assert out["waves"]["q1_revenue:1"]["bucket"] == 4
    assert out["waves"]["total_price:5"]["bucket"] == 8
    # each statement: 6 waves, 23 calls, padded to 4+4+4+4+8+8 = 32 rows
    assert out["timing"] == {"sharded_waves": 12, "sharded_calls": 46,
                             "pad_calls": 18}


def test_dispatch_span_names_its_devices(on_four_devices):
    assert on_four_devices["dispatch_devices"] == [4]


def test_open_x4_cell_runs_every_wave_on_the_mesh(on_four_devices):
    cell = on_four_devices["cell"]
    assert cell["correct"] and cell["failed"] == 0 and cell["attempted"]
    assert cell["count"] == 4
    assert cell["metrics"] == ["call_p50_ms.open", "setup_s"]
    sched = cell["sched"]
    assert sched["batches"] > 0
    assert sched["sharded_waves"] == sched["batches"]
    assert sched["sharded_calls"] == sched["drained"] == cell["attempted"]
    assert sched["pad_calls"] > 0

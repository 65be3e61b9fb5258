"""Faults planted under the timed path: the harness's comparison must
call each run not correct.  One-chip faults run in this process; the
exchange between chips needs four virtual devices and runs in a child."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import jax.numpy as jnp
import pytest

from _chip_tiny import CHIP, ROOT, run_tiny, tiny_spec


def _state_unchanged(orig):
    def serve(self, ops, ids, state, received):
        _, resp = orig(self, ops, ids, state, received)
        return state, resp
    return serve


def _half_the_batch(orig):
    def serve(self, ops, ids, state, received):
        n = received.valid.shape[0]
        keep = received.valid & (jnp.arange(n) % 2 == 0)
        return orig(self, ops, ids, state, received._replace(valid=keep))
    return serve


def _answer_altered(orig):
    def serve(self, ops, ids, state, received):
        state, resp = orig(self, ops, ids, state, received)
        return state, {**resp, "value": resp["value"].at[0].add(1.0)}
    return serve


@pytest.mark.parametrize("fault", [_state_unchanged, _half_the_batch,
                                   _answer_altered])
def test_fault_under_the_round_is_caught(monkeypatch, fault):
    from repro.core.kvstore import KVTableServe
    monkeypatch.setattr(KVTableServe, "serve_lax",
                        fault(KVTableServe.serve_lax))
    out = run_tiny(tiny_spec("memcached16", "memcached_zipf"))
    assert not out["correct"], out["checks"]


CHILD = r"""
import json, sys
sys.path.insert(0, sys.argv[1])
from _chip_tiny import run_tiny, tiny_spec
out = {}
spec = tiny_spec("ycsb1kb-host", "ycsb_b", chips=4)
out["sound"] = run_tiny(spec)["correct"]
import repro.core.channel as ch
ch._a2a = lambda x, axis, n: x          # the exchange between chips left out
out["no_exchange"] = run_tiny(tiny_spec("ycsb1kb-host", "ycsb_b", chips=4))
print(json.dumps({"sound": out["sound"],
                  "no_exchange": out["no_exchange"]["correct"]}))
"""


def test_exchange_left_out_is_caught_on_four_devices():
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": os.path.join(ROOT, "src")}
    p = subprocess.run(
        [sys.executable, "-c", CHILD, os.path.join(CHIP, "tests")],
        capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got == {"sound": True, "no_exchange": False}, p.stderr[-3000:]


SETUP_ORDER_CHILD = r"""
import json, sys, weakref
sys.path.insert(0, sys.argv[1])
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from _chip_tiny import run_tiny, tiny_spec
import harness
import repro.core.kvstore as kvstore
from repro.core.trust import Trust

# a bytes limit under the table's size sends the harness down its host path
type(jax.devices()[0]).memory_stats = lambda self: {"bytes_limit": 1 << 20}
devices = jax.devices()[:4]
sharding = NamedSharding(Mesh(np.array(devices).reshape(1, 4),
                              ("data", "model")), P(("data", "model")))


zero_tables = []


class LateZeros:
    # jax.numpy, but the store's zero table lands late on devices 1-3, as
    # a host copy does on the chip; the default device's shard is ready at
    # once, since the CPU queues what the harness puts there behind it
    def __getattr__(self, name):
        return getattr(jnp, name)

    @staticmethod
    def zeros(shape, dtype=jnp.float32):
        rows = (shape[0] // 4,) + tuple(shape[1:])
        late = jax.jit(lambda: jnp.where(jax.lax.fori_loop(
            0, 20000, lambda i, x: x * 0.999 + 0.001,
            jnp.ones(rows, dtype)) > 2.0, 1.0, 0.0).astype(dtype))
        shards = [jax.device_put(jnp.zeros(rows, dtype), devices[0])]
        for d in devices[1:]:
            with jax.default_device(d):
                shards.append(late())
        table = jax.make_array_from_single_device_arrays(shape, sharding,
                                                         shards)
        zero_tables.append(weakref.ref(table))
        return table


store_init = kvstore.DelegatedKVStore.__init__


def late_init(self, *a, **k):
    kvstore.jnp = LateZeros()
    try:
        store_init(self, *a, **k)
    finally:
        kvstore.jnp = jnp


kvstore.DelegatedKVStore.__init__ = late_init
ready = []
install = Trust.install_trustee_state


def recording_install(self, logical):
    ready.append(all(s.data.is_ready()
                     for s in self.state()["table"].addressable_shards))
    return install(self, logical)


Trust.install_trustee_state = recording_install
held = []
cell_run = harness.Cell.run


def recording_run(self, *a, **k):
    held.append([r() is not None for r in zero_tables])
    return cell_run(self, *a, **k)


harness.Cell.run = recording_run
out = run_tiny(tiny_spec("ycsb1kb-host", "ycsb_b", chips=4))
print(json.dumps({"ready_at_install": ready, "held_at_window": held,
                  "correct": out["correct"]}))
"""


def test_store_table_is_on_its_devices_before_the_initial_table():
    """On the host path the store's zero table is still being copied to
    the devices when its constructor returns; the harness waits for it
    before it installs the initial table, so no copy runs on into the
    window, and nothing holds the zero table once the window starts."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": os.path.join(ROOT, "src")}
    p = subprocess.run(
        [sys.executable, "-c", SETUP_ORDER_CHILD, os.path.join(CHIP, "tests")],
        capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got == {"ready_at_install": [True], "held_at_window": [[False]],
                   "correct": True}, p.stderr[-3000:]

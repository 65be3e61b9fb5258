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

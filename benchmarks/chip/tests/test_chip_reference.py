"""The benchmark's plain KV reference against the program's sequential
oracle, on seeded random traces with inactive rows."""
from __future__ import annotations

import numpy as np
import pytest

import _chip_tiny  # noqa: F401  (puts the benchmark on sys.path)
import traffic as tf
from kvref import KVReference


def lane(rng, op, n, n_keys, hot):
    keys = np.where(rng.random(n) < 0.5, rng.integers(0, hot, n),
                    rng.integers(0, n_keys, n)).astype(np.int32)
    mask = rng.random(n) < 0.8
    keys[~mask] = -1
    return keys, mask


@pytest.mark.parametrize("ops,seed", [(("get", "put"), 1), (("get", "put"), 2),
                                      (("get", "put", "add", "cas"), 3)])
def test_reference_agrees_with_sequential_oracle(ops, seed):
    """Each wave submits its ops in the serve's phase order (GET, PUT, ADD,
    CAS), where the sequential oracle applied batch by batch has the
    wave's semantics."""
    from repro.core import SequentialKVReference
    rng = np.random.default_rng(seed)
    n_keys, width = 300, 3
    data = tf.make_table_data(seed, width, 64)
    ref = KVReference(n_keys, data, 1, owner_last=True,
                      with_add="add" in ops)
    seq = SequentialKVReference(n_keys, width)
    seq.prefill(data.initial(np.arange(n_keys)))
    for wave in range(40):
        lanes, want = [], []
        for op in ops:
            n = int(rng.integers(1, 64))
            keys, mask = lane(rng, op, n, n_keys, hot=8)
            d = {"client": 0, "op": op, "keys": keys, "mask": mask}
            if op in ("put", "cas"):
                start = int(rng.integers(0, tf.VALUE_POOL_ROWS - 64))
                d["rows"] = np.arange(start, start + n)
                vals = data.put_pool[d["rows"]]
            if op == "get":
                want.append({"value": seq.get(keys)})
            elif op == "put":
                seq.put(keys, vals)
                want.append({"flag": np.zeros(n, np.int32)})
            elif op == "add":
                d["delta"] = data.add_pool[:n]
                want.append({"value": seq.add(keys, d["delta"])})
            else:
                live = seq.table[np.maximum(keys, 0)]
                d["expect"] = np.where(rng.random((n, 1)) < 0.5, live,
                                       data.put_pool[:n])
                flag, old = seq.cas(keys, d["expect"], vals)
                want.append({"value": old, "flag": flag})
            lanes.append(d)
        got = ref.wave(lanes)
        for g, w, d in zip(got, want, lanes):
            for f, v in w.items():
                np.testing.assert_array_equal(g[f], v, err_msg=(wave, d["op"]))
    np.testing.assert_array_equal(ref.rows(np.arange(n_keys)), seq.dump())


def test_owner_rows_commit_after_other_clients():
    """With four trustees the owner's own PUT to a key wins over an
    earlier-numbered client's, and otherwise the higher client wins."""
    data = tf.make_table_data(5, 2, 8)
    ref = KVReference(16, data, 4, owner_last=True, with_add=False)
    one = lambda c, k, row: {"client": c, "op": "put",
                             "keys": np.array([k], np.int32),
                             "mask": np.array([True]),
                             "rows": np.array([row])}
    # key 5 is owned by client 1; key 6 by client 2, which does not write
    ref.wave([one(0, 5, 10), one(1, 5, 11), one(3, 5, 13),
              one(0, 6, 20), one(3, 6, 23), one(1, 6, 21)])
    np.testing.assert_array_equal(ref.rows(np.array([5, 6])),
                                  data.put_pool[[11, 23]])

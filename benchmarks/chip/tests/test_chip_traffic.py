"""The chip benchmark's generators: YCSB's scrambled zipfian, Zipf, the
op interleave, Poisson arrivals and the table data."""
from __future__ import annotations

import math

import numpy as np
import pytest

import _chip_tiny  # noqa: F401  (puts the benchmark on sys.path)
import traffic as tf


def fnv_plain(v: int) -> int:
    """YCSB Utils.fnvhash64 with Java's long arithmetic, one value."""
    h = 0xCBF29CE484222325
    for _ in range(8):
        h ^= v & 0xFF
        v >>= 8
        h = (h * 1099511628211) % (1 << 64)
    if h >= 1 << 63:
        h -= 1 << 64
    return abs(h) if h != -(1 << 63) else h


@pytest.mark.parametrize("v", [0, 1, 2, 255, 256, 12345678901, 10**10])
def test_fnvhash64_matches_java_long_arithmetic(v):
    assert int(tf.fnvhash64(np.array([v]))[0]) == fnv_plain(v)


def test_scrambled_zipfian_head_frequencies_match_closed_form():
    """Rank 0 has probability 1/zetan and rank 1 0.5^theta/zetan (YCSB's
    ZipfianGenerator); the scramble maps rank r to fnv(r) % (n + 1)."""
    n, count = 4_194_304, 2_000_000
    keys = tf.ycsb_scrambled_zipfian_keys(np.random.default_rng(3), n,
                                          count)
    assert keys.min() >= 0 and keys.max() < n
    for rank, p in ((0, 1 / tf.YCSB_ZETAN),
                    (1, 0.5 ** 0.99 / tf.YCSB_ZETAN)):
        key = fnv_plain(rank) % (n + 1)
        got = np.count_nonzero(keys == key) / count
        sd = math.sqrt(p * (1 - p) / count)
        assert abs(got - p) < 5 * sd, (rank, got, p)


def test_zipf_head_frequency_matches_closed_form():
    n, count, alpha = 1_000_000, 1_000_000, 1.0
    keys = tf.zipf_keys(np.random.default_rng(4), n, count, alpha)
    h = np.sum(np.arange(1, n + 1, dtype=np.float64) ** -alpha)
    for k in (0, 1, 9):
        p = (k + 1) ** -alpha / h
        got = np.count_nonzero(keys == k) / count
        assert abs(got - p) < 5 * math.sqrt(p * (1 - p) / count), (k, got)


@pytest.mark.parametrize("shares", [{"get": 95, "put": 5},
                                    {"get": 50, "put": 50},
                                    {"get": 6, "put": 2, "add": 1, "cas": 1}])
def test_op_interleave_keeps_every_window_inside_its_lanes(shares):
    w = 1000
    s = tf.ClientStream(np.random.default_rng(0), 5000, shares,
                        {"kind": "uniform"}, w, 4)
    total = sum(shares.values())
    for op, n in shares.items():
        assert abs(s.lane[op] - n * w / total) <= 2
    for start in range(0, 3 * s.period):
        lanes = s.lanes(start, start + w)
        assert sum(ln.n for ln in lanes) == w
        for ln in lanes:
            assert (ln.keys[ln.n:] == -1).all()
            assert (ln.keys[:ln.n] >= 0).all()
            assert ln.mask.sum() == ln.n


def test_lanes_walk_each_sub_stream_in_order():
    s = tf.ClientStream(np.random.default_rng(1), 5000,
                        {"get": 3, "put": 1}, {"kind": "uniform"}, 64, 2)
    seen = {"get": [], "put": []}
    for k in range(6):                   # past one cycle of the stream
        for ln in s.lanes(k * 64, (k + 1) * 64):
            seen[ln.op].extend(ln.keys[:ln.n])
    for op, keys in seen.items():
        n = s.sub_len[op]
        assert keys == list(np.resize(s.sub_keys[op][:n], len(keys)))


def test_poisson_arrivals_rate_and_order():
    t = tf.poisson_arrivals(np.random.default_rng(2), 50_000.0, 2.0)
    assert (np.diff(t) >= 0).all() and t[-1] < 2.0
    assert abs(len(t) - 100_000) < 5 * math.sqrt(100_000)


def test_client_ranges_deal_round_robin():
    r = tf.client_ranges(10, 23, 4)
    got = sorted(c + 4 * j for c, (a, b) in enumerate(r)
                 for j in range(a, b))
    assert got == list(range(10, 23))


def test_values_exact_in_f32_and_not_in_bf16():
    import ml_dtypes
    d = tf.make_table_data(9, 250, 16)
    init = d.initial(np.arange(4096))
    assert (init >= 0).all() and (d.put_pool < 0).all()
    for x in (init, d.put_pool):
        assert (np.abs(x) < 2 ** 24).all() and (x == np.round(x)).all()
        bf = x.astype(ml_dtypes.bfloat16).astype(np.float32)
        assert np.mean(bf != x) > 0.9
    # rows of distinct keys differ
    assert len(np.unique(init[:, 0])) == 4096


@pytest.mark.parametrize("n_keys,n_trustees", [(1000, 1), (1001, 4)])
def test_device_table_is_the_closed_form_owner_major(n_keys, n_trustees):
    """The table made on the device holds key k's closed-form row at
    trustee k % T, local row k // T, and zeros past the key space."""
    import jax.numpy as jnp
    import harness
    d = tf.make_table_data(11, 6, 16)
    n_pad = -(-n_keys // n_trustees) * n_trustees
    got = np.asarray(harness.initial_table(
        d, n_keys, n_trustees, jnp.zeros((n_pad, 6)), jnp.float32))
    n_local = n_pad // n_trustees
    keys = np.arange(n_pad)
    pos = (keys % n_trustees) * n_local + keys // n_trustees
    want = np.zeros((n_pad, 6), np.float32)
    want[pos[:n_keys]] = d.initial(keys[:n_keys])
    np.testing.assert_array_equal(got, want)

"""Traffic and data of the chip benchmark, all drawn from ``--seed``.

Everything here is vectorised NumPy and runs at set-up.  Inside the
measured window the harness only slices what was made here.

* Key distributions: Zipf over key ids (the distribution of
  ``repro.core.routing.zipf_probs``, sampled by inverse CDF instead of
  ``rng.choice``), YCSB's scrambled zipfian exactly as YCSB implements it
  (``ZipfianGenerator`` over 10^10 items, FNV-1 64-bit scramble), and
  uniform.
* Arrivals: a Poisson process at a fixed rate.
* Op mix: a fixed interleave at the traffic file's integer shares, so every
  window of a wave's length holds the same op counts to within one row and
  the program's batch shapes never change.
* Data: the initial table is a closed-form function of (seed, key, column)
  and PUT values come from a pool made from the seed.  All values are
  integers exact in float32 and mostly not exact in bfloat16; initial
  values are >= 0 and PUT values < 0, so a stale row cannot pass for a
  written one.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, List

import numpy as np

OPS = ("get", "put", "add", "cas")

# YCSB ScrambledZipfianGenerator constants (site.ycsb.generator)
YCSB_ITEM_COUNT = 10_000_000_000
YCSB_ZETAN = 26.46902820178302          # zeta(10^10, 0.99), as YCSB hard-codes
FNV_OFFSET_BASIS_64 = 0xCBF29CE484222325
FNV_PRIME_64 = 1099511628211

VALUE_POOL_ROWS = 65536                  # distinct PUT rows, cycled
VALUE_BITS = 23
_VALUE_SPAN = 1 << VALUE_BITS            # |values| < 2^23 + 2^23: exact in f32


# ---------------------------------------------------------------------------
# Key distributions
# ---------------------------------------------------------------------------

def zipf_keys(rng: np.random.Generator, n_keys: int, count: int,
              alpha: float) -> np.ndarray:
    """Zipf over key ids: P(k) is proportional to (k + 1)^-alpha, key 0 the
    hottest."""
    cdf = np.cumsum(np.arange(1, n_keys + 1, dtype=np.float64) ** -alpha)
    cdf /= cdf[-1]
    keys = np.searchsorted(cdf, rng.random(count), side="right")
    return np.minimum(keys, n_keys - 1).astype(np.int32)


def fnvhash64(values: np.ndarray) -> np.ndarray:
    """YCSB ``Utils.fnvhash64``: FNV-1 over the 8 low-to-high octets of a
    long, then ``Math.abs`` of the signed result."""
    val = np.asarray(values, np.int64).astype(np.uint64)
    h = np.full(val.shape, FNV_OFFSET_BASIS_64, np.uint64)
    prime = np.uint64(FNV_PRIME_64)
    for _ in range(8):
        h = (h ^ (val & np.uint64(0xFF))) * prime
        val = val >> np.uint64(8)
    return np.abs(h.view(np.int64))


def ycsb_zipfian_ranks(rng: np.random.Generator, count: int,
                       items: int = YCSB_ITEM_COUNT + 1,
                       zetan: float = YCSB_ZETAN,
                       theta: float = 0.99) -> np.ndarray:
    """YCSB ``ZipfianGenerator.nextLong`` (Gray et al.'s closed form)."""
    alpha = 1.0 / (1.0 - theta)
    zeta2 = 1.0 + 0.5 ** theta
    eta = (1.0 - (2.0 / items) ** (1.0 - theta)) / (1.0 - zeta2 / zetan)
    u = rng.random(count)
    uz = u * zetan
    ret = np.floor(items * np.power(eta * u - eta + 1.0, alpha))
    ret = ret.astype(np.int64)
    ret[uz < 1.0 + 0.5 ** theta] = 1
    ret[uz < 1.0] = 0
    return ret


def ycsb_scrambled_zipfian_keys(rng: np.random.Generator, n_keys: int,
                                count: int, theta: float = 0.99
                                ) -> np.ndarray:
    """YCSB ``requestdistribution=zipfian`` for a workload without inserts:
    ``ScrambledZipfianGenerator(0, recordcount)`` draws over
    ``recordcount + 1`` ids and ``CoreWorkload.nextKeynum`` redraws the one
    id past the loaded records."""
    if theta != 0.99:
        raise ValueError("YCSB's scrambled zipfian hard-codes zetan for "
                         f"theta 0.99, got {theta}")
    out = np.empty(count, np.int64)
    todo = np.arange(count)
    while todo.size:
        k = fnvhash64(ycsb_zipfian_ranks(rng, todo.size, theta=theta)) \
            % (n_keys + 1)
        ok = k < n_keys
        out[todo[ok]] = k[ok]
        todo = todo[~ok]
    return out.astype(np.int32)


def draw_keys(rng: np.random.Generator, n_keys: int, count: int,
              dist: Dict) -> np.ndarray:
    kind = dist["kind"]
    if kind == "zipf":
        return zipf_keys(rng, n_keys, count, float(dist["alpha"]))
    if kind == "ycsb_scrambled_zipfian":
        return ycsb_scrambled_zipfian_keys(rng, n_keys, count,
                                           float(dist["theta"]))
    if kind == "uniform":
        return rng.integers(0, n_keys, count, dtype=np.int64) \
            .astype(np.int32)
    raise ValueError(f"unknown key distribution {kind!r}")


def poisson_arrivals(rng: np.random.Generator, rate: float,
                     seconds: float) -> np.ndarray:
    """Arrival offsets (s) of a Poisson process at ``rate``/s in
    ``[0, seconds)``."""
    n = int(rate * seconds + 10 * math.sqrt(rate * seconds) + 16)
    t = np.cumsum(rng.exponential(1.0 / rate, n))
    while t[-1] < seconds:
        t = np.concatenate([t, t[-1] + np.cumsum(
            rng.exponential(1.0 / rate, n))])
    return t[t < seconds]


# ---------------------------------------------------------------------------
# Op mix
# ---------------------------------------------------------------------------

def op_pattern(shares: Dict[str, int]) -> np.ndarray:
    """One period of the op interleave: op indices (into ``OPS``) chosen by
    smooth weighted round robin, so every window holds each op at its
    share to within one row."""
    w = np.array([int(shares.get(op, 0)) for op in OPS], np.int64)
    if (w < 0).any() or w.sum() <= 0:
        raise ValueError(f"op shares must be >= 0 and not all 0: {shares}")
    w //= np.gcd.reduce(w[w > 0])
    total = int(w.sum())
    cur = np.zeros(len(OPS), np.int64)
    out = np.empty(total, np.int8)
    for i in range(total):
        cur += w
        j = int(np.argmax(cur))
        cur[j] -= total
        out[i] = j
    return out


# ---------------------------------------------------------------------------
# Data: the initial table and the value pools
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class TableData:
    """Closed-form initial rows and the PUT / ADD value pools of one run.

    ``initial(keys)`` is ``k_term[key] + c_term[col]`` with both terms
    integers in [0, 2^23): a bijection of the key's low 23 bits, so rows
    of distinct keys differ."""
    seed: int
    width: int
    put_pool: np.ndarray     # (VALUE_POOL_ROWS + slack, W) f32, values < 0
    add_pool: np.ndarray     # (VALUE_POOL_ROWS + slack, W) f32, in [1, 8)

    @property
    def terms(self):
        """(a, b, c, d): ``k_term = (key * a + b) mod 2^23`` and
        ``c_term = (col * c + d) mod 2^23``."""
        a, b, c, d = np.random.default_rng([self.seed, 17]).integers(
            0, _VALUE_SPAN, 4)
        return int(a) | 1, int(b), int(c), int(d)

    def k_term(self, keys: np.ndarray) -> np.ndarray:
        a, b, _, _ = self.terms
        k = np.asarray(keys, np.int64)
        return ((k * a + b) % _VALUE_SPAN).astype(np.float32)

    def c_term(self) -> np.ndarray:
        _, _, c, d = self.terms
        col = np.arange(self.width, dtype=np.int64)
        return ((col * c + d) % _VALUE_SPAN).astype(np.float32)

    def initial(self, keys: np.ndarray) -> np.ndarray:
        return self.k_term(keys)[:, None] + self.c_term()[None, :]


def make_table_data(seed: int, width: int, slack: int) -> TableData:
    """Pools of ``VALUE_POOL_ROWS`` rows, each followed by a copy of its
    first ``slack`` rows so that any lane-long run of rows from any start
    is one contiguous slice."""
    rng = np.random.default_rng([seed, 23])
    n = VALUE_POOL_ROWS
    put = -rng.integers(1, _VALUE_SPAN, (n, width)).astype(np.float32)
    add = rng.integers(1, 8, (n, width)).astype(np.float32)
    wrap = lambda p: np.concatenate([p, p[:slack]], 0)
    return TableData(seed, width, wrap(put), wrap(add))


# ---------------------------------------------------------------------------
# Request streams and waves
# ---------------------------------------------------------------------------

@dataclass
class Lane:
    """One client's rows of one op in one wave, in the program's batch
    shape: ``keys`` (-1 where inactive), ``mask`` (True where active),
    ``first`` (absolute index of the lane's first row in the op's
    sub-stream; row i is sub-stream row ``first + i``)."""
    op: str
    keys: np.ndarray
    mask: np.ndarray
    first: int
    n: int


class ClientStream:
    """One client's request stream.

    Request ``j`` has op ``pattern[j % period]``.  Each op has its own key
    sub-stream of ``sub_len`` keys, cycled; the sub-stream arrays carry a
    copy of their first ``lane`` keys at the end, so a lane is a slice."""

    def __init__(self, rng: np.random.Generator, n_keys: int,
                 shares: Dict[str, int], dist: Dict, wave_rows: int,
                 distinct_waves: int):
        self.pattern = op_pattern(shares)
        self.period = len(self.pattern)
        per_period = np.bincount(self.pattern, minlength=len(OPS))
        # prefix[o][r]: requests of op o among pattern positions [0, r)
        self.prefix = np.zeros((len(OPS), self.period + 1), np.int64)
        for o in range(len(OPS)):
            self.prefix[o, 1:] = np.cumsum(self.pattern == o)
        self.per_period = per_period
        # lane length: the most rows of an op in any window of wave_rows
        self.lane = {}
        periods = -(-wave_rows // self.period) + 2
        onehot = np.eye(len(OPS), dtype=np.int64)[self.pattern]
        tiled = np.concatenate([np.zeros((1, len(OPS)), np.int64),
                                np.cumsum(np.tile(onehot, (periods, 1)),
                                          axis=0)], 0)
        for o, op in enumerate(OPS):
            if per_period[o]:
                c = tiled[:, o]
                self.lane[op] = int((c[wave_rows:wave_rows + self.period]
                                     - c[:self.period]).max())
        n_req = max(1, distinct_waves * wave_rows // self.period) \
            * self.period
        self.sub_len = {}
        self.sub_keys = {}
        for o, op in enumerate(OPS):
            if op not in self.lane:
                continue
            n = n_req // self.period * int(per_period[o])
            keys = draw_keys(rng, n_keys, n, dist)
            self.sub_len[op] = n
            self.sub_keys[op] = np.concatenate(
                [keys, np.resize(keys, self.lane[op])])

    @property
    def ops(self) -> List[str]:
        return list(self.lane)

    def op_pos(self, op: str, j: int) -> int:
        """Requests of ``op`` among stream requests [0, j)."""
        q, r = divmod(j, self.period)
        o = OPS.index(op)
        return q * int(self.per_period[o]) + int(self.prefix[o, r])

    def lanes(self, start: int, end: int) -> List[Lane]:
        out = []
        for op in self.ops:
            a, b = self.op_pos(op, start), self.op_pos(op, end)
            n, size = b - a, self.lane[op]
            if n > size:
                raise ValueError(f"{end - start} requests hold {n} {op} rows,"
                                 f" more than the lane's {size}")
            off = a % self.sub_len[op]
            keys = self.sub_keys[op][off:off + size].copy()
            keys[n:] = -1
            out.append(Lane(op, keys, np.arange(size) < n, a, n))
        return out


def client_ranges(start: int, end: int, n_clients: int) -> np.ndarray:
    """Global requests [start, end) dealt round-robin to clients: request
    j goes to client j % C as that client's request j // C.  Returns the
    (C, 2) client-local ranges."""
    c = np.arange(n_clients)
    return np.stack([(start - c + n_clients - 1) // n_clients,
                     (end - c + n_clients - 1) // n_clients], 1)

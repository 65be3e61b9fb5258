"""Plain reference of the delegated KV store's semantics.

A copy kept with the benchmark, so that no change to the program under
test can change what it is compared with.  It imports nothing of the
program.  It applies one wave (one engine round) at a time, as the
configuration files state the guarantees:

* GET reads the table as it was when the wave arrived.
* PUT commits last-writer-wins; ADD then adds, answering each row with the
  value before its own delta; CAS then compares against the table after
  the ADDs and the last matching row of each key commits.
* Rows of one key are served in the owning trustee's order: rows from
  other clients in client order, then the owner's own rows (the local
  shortcut appends them last, DESIGN.md section 4).  Each client's rows
  keep their lane order.  With one client this is plain request order.
* Inactive rows (mask False) change nothing and answer zeros.

The table is held as a reference per key into the run's PUT value pool
(-1 for the closed-form initial row) plus, when the traffic has ADDs, a
dense delta table, so that only what the window wrote costs memory.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from traffic import TableData


class KVReference:

    def __init__(self, n_keys: int, data: TableData, n_trustees: int,
                 owner_last: bool, with_add: bool):
        self.data = data
        self.n_trustees = n_trustees
        self.owner_last = owner_last
        self.src = np.full(n_keys, -1, np.int32)
        self.delta = (np.zeros((n_keys, data.width), np.float32)
                      if with_add else None)

    def rows(self, keys: np.ndarray) -> np.ndarray:
        """Current rows of ``keys`` (all >= 0)."""
        keys = np.asarray(keys, np.int64)
        out = self.data.initial(keys)
        src = self.src[keys]
        w = src >= 0
        out[w] = self.data.put_pool[src[w]]
        if self.delta is not None:
            out += self.delta[keys]
        return out

    def _serve_rank(self, client: np.ndarray, pos: np.ndarray,
                    keys: np.ndarray) -> np.ndarray:
        c = client.astype(np.int64)
        if self.owner_last:
            own = (keys.astype(np.int64) % self.n_trustees) == c
            c = np.where(own, np.iinfo(np.int32).max, c)
        return c * (1 << 32) + pos

    def _ordered(self, parts: List[Tuple[int, np.ndarray, np.ndarray]]):
        """Active rows of several clients' lanes of one op, sorted by key
        and then by serve order.  ``parts``: (client, keys, mask).
        Returns (keys, index into the concatenated lanes, True at the
        last row of each key)."""
        keys = np.concatenate([k for _, k, _ in parts]).astype(np.int64)
        mask = np.concatenate([m for _, _, m in parts])
        client = np.concatenate([np.full(len(k), c) for c, k, _ in parts])
        pos = np.concatenate([np.arange(len(k)) for _, k, _ in parts])
        idx = np.nonzero(mask)[0]
        rank = self._serve_rank(client[idx], pos[idx], keys[idx])
        order = idx[np.lexsort((rank, keys[idx]))]
        k = keys[order]
        seg_end = np.ones(len(k), bool)
        seg_end[:-1] = k[1:] != k[:-1]
        return k, order, seg_end

    def wave(self, lanes: Sequence[Dict], answer: bool = True
             ) -> List[Optional[Dict[str, np.ndarray]]]:
        """Apply one wave.  ``lanes``: one dict per (client, op) batch with
        ``client``, ``op``, ``keys``, ``mask`` and the op's payload:
        ``rows`` (put-pool row per row) for PUT, ``delta`` for ADD,
        ``expect`` and ``rows`` for CAS.  Returns each batch's expected
        response (``value`` for GET, ADD and CAS, ``flag`` for PUT and
        CAS), in the same order; with ``answer=False`` only the table
        moves and no response is made."""
        by_op: Dict[str, List[int]] = {}
        for i, ln in enumerate(lanes):
            by_op.setdefault(ln["op"], []).append(i)
        resp: List[Optional[Dict[str, np.ndarray]]] = [None] * len(lanes)
        cat = lambda ids, f: np.concatenate([lanes[i][f] for i in ids])
        parts = lambda ids: [(lanes[i]["client"], lanes[i]["keys"],
                              lanes[i]["mask"]) for i in ids]

        def split(ids, full):
            off = 0
            for i in ids:
                n = len(lanes[i]["keys"])
                resp[i] = {k: v[off:off + n] for k, v in full.items()}
                off += n

        width = self.data.width
        for i in by_op.get("get", []) if answer else ():
            ln = lanes[i]
            out = np.zeros((len(ln["keys"]), width), np.float32)
            out[ln["mask"]] = self.rows(ln["keys"][ln["mask"]])
            resp[i] = {"value": out}
        if "put" in by_op:
            ids = by_op["put"]
            k, order, last = self._ordered(parts(ids))
            self.src[k[last]] = cat(ids, "rows")[order[last]]
            if self.delta is not None:
                self.delta[k[last]] = 0
            for i in ids if answer else ():
                resp[i] = {"flag": np.zeros(len(lanes[i]["keys"]), np.int32)}
        if "add" in by_op:
            ids = by_op["add"]
            k, order, last = self._ordered(parts(ids))
            delta = cat(ids, "delta")[order]
            if answer:
                excl = np.cumsum(delta, axis=0, dtype=np.float64) - delta
                seg_first = np.ones(len(k), bool)
                seg_first[1:] = k[1:] != k[:-1]
                start = np.maximum.accumulate(
                    np.where(seg_first, np.arange(len(k)), 0))
                before = excl - excl[start]
                full = np.zeros((len(cat(ids, "keys")), width), np.float32)
                full[order] = self.rows(k) + before.astype(np.float32)
                split(ids, {"value": full})
            np.add.at(self.delta, k, delta)
        if "cas" in by_op:
            ids = by_op["cas"]
            keys = cat(ids, "keys").astype(np.int64)
            mask = cat(ids, "mask")
            n = len(keys)
            cur = np.zeros((n, width), np.float32)
            cur[mask] = self.rows(keys[mask])
            ok = mask & np.all(cur == cat(ids, "expect"), axis=1)
            hit = [(c, kk, m & o) for (c, kk, m), o in zip(
                parts(ids), np.split(ok, np.cumsum(
                    [len(lanes[i]["keys"]) for i in ids])[:-1]))]
            k, order, last = self._ordered(hit)
            self.src[k[last]] = cat(ids, "rows")[order[last]]
            if self.delta is not None:
                self.delta[k[last]] = 0
            split(ids, {"value": cur, "flag": ok.astype(np.int32)})
        return resp

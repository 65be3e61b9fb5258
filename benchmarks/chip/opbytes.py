"""HBM bytes that a wave's ops require, whatever implements them.

Counted from the ops alone, per active row, so the count reads the same
work for every implementation of the round:

* GET: the key, the table row read, the response row written.
* PUT: the key, the payload row read, the table row written, the
  acknowledgement flag written.
* ADD: the key, the delta row read, the table row read and written, the
  response row (the value before the delta) written.
* CAS: the key, the expected and the new row read, the table row read,
  the response row and the flag written.  The new row is written only
  where the compare matches; it is not counted, so the count stays a
  lower bound.

Keys and flags are int32.  Rows are ``width`` values of ``dtype_bytes``.
"""
from __future__ import annotations

from typing import Dict

KEY_BYTES = 4
FLAG_BYTES = 4


def op_bytes(op: str, width: int, dtype_bytes: int) -> int:
    row = width * dtype_bytes
    if op == "get":
        return KEY_BYTES + 2 * row
    if op == "put":
        return KEY_BYTES + 2 * row + FLAG_BYTES
    if op == "add":
        return KEY_BYTES + 4 * row
    if op == "cas":
        return KEY_BYTES + 4 * row + FLAG_BYTES
    raise ValueError(f"unknown op {op!r}")


def wave_bytes(rows_per_op: Dict[str, int], width: int,
               dtype_bytes: int) -> int:
    """Bytes one wave requires: ``rows_per_op`` counts its active rows."""
    return sum(n * op_bytes(op, width, dtype_bytes)
               for op, n in rows_per_op.items())

"""Bytes per op behind round_roofline.ops."""
from __future__ import annotations

import pytest

import _chip_tiny  # noqa: F401  (puts the benchmark on sys.path)
import opbytes


@pytest.mark.parametrize("width,dtype_bytes,want", [
    (4, 4, {"get": 36, "put": 40, "add": 68, "cas": 72}),
    (250, 4, {"get": 2004, "put": 2008, "add": 4004, "cas": 4008}),
    (250, 2, {"get": 1004, "put": 1008, "add": 2004, "cas": 2008}),
])
def test_op_bytes_per_kind_and_width(width, dtype_bytes, want):
    for op, n in want.items():
        assert opbytes.op_bytes(op, width, dtype_bytes) == n


def test_wave_bytes_sums_active_rows():
    # the memcached wave: 15,565 GETs and 819 PUTs of 16-byte rows
    assert opbytes.wave_bytes({"get": 15565, "put": 819}, 4, 4) \
        == 15565 * 36 + 819 * 40


def test_unknown_op_is_an_error():
    with pytest.raises(ValueError):
        opbytes.op_bytes("scan", 4, 4)

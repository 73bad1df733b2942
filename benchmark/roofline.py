"""Peaks of the chip and the bytes a scan has to touch, from shapes.

`logical_bytes` is rows x the bytes per row that the mix's file fixes for
the statement (the touched columns' widths under the TPU dtype policy):
the work the query asks for, whatever implements it. It is not what the
implementation moves; a share above 100 % would say the count is wrong.
"""

from __future__ import annotations

import json
import os


def peaks_for(device_kind: str) -> dict:
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "peaks.json")
    with open(path, encoding="utf-8") as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json: add it with its source")
    return table[device_kind]


def logical_bytes(statements) -> int:
    """Bytes the completed queries among `statements` (records with
    `rows_read` and the mix's `bytes_per_row`) had to read."""
    return sum(int(r["rows_read"]) * int(r["bytes_per_row"])
               for r in statements
               if r.get("bytes_per_row"))


def roofline_pct(n_bytes: int, busy_s: float, peaks: dict):
    """The least time the chip could take for `n_bytes`, over the seconds
    it was busy. None where nothing ran or nothing was read."""
    if not n_bytes or not busy_s or busy_s <= 0:
        return None
    return 100.0 * (n_bytes / peaks["hbm_bytes_per_s"]) / busy_s

"""Bytes a unit of work needs, from its shapes alone.

These count what the work itself must move through HBM, never the
padded or repeated traffic of one implementation, so a kernel that
re-reads or pads shows up as a low share of its roofline, and the share
cannot pass 100% unless the time leaves out part of the work.
"""

from __future__ import annotations

import math


def vector_wave_bytes(rows: int, dim: int) -> int:
    """Exact dense top-k of one wave of queries over ``rows`` stored
    float32 vectors of width ``dim``: every stored vector must be read
    once, whatever the number of queries in the wave (they can all be
    scored in the same pass), plus one live bit per row.  The queries and
    the k winners per query are a few KiB and are left out."""
    return rows * dim * 4 + math.ceil(rows / 8)

"""Wrapper for the SSD chunk kernel (``interpret=True`` off-TPU)."""

from __future__ import annotations

from .. import check_backend
from .ssd_scan import ssd_chunk_fwd


def ssd_chunk(C, B, x, dt, da, *, interpret: bool = False):
    check_backend(interpret)
    return ssd_chunk_fwd(C, B, x, dt, da, interpret=interpret)

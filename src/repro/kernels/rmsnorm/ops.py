"""Wrapper for the fused RMSNorm kernel (``interpret=True`` off-TPU)."""

from __future__ import annotations

from .. import check_backend
from .rmsnorm import rmsnorm_fwd


def rmsnorm(x, scale, eps: float = 1e-6, *, interpret: bool = False):
    check_backend(interpret)
    return rmsnorm_fwd(x, scale, eps, interpret=interpret)

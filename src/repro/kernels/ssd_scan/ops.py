"""Jit'd public wrapper for the SSD scan."""
from __future__ import annotations

from repro.kernels.ssd_scan.kernel import ssd_scan
from repro.kernels.ssd_scan.ref import ssd_ref_chunked, ssd_ref_sequential


def ssd(x, dt, A, B, C, *, chunk=64, use_kernel=True, interpret=False):
    if use_kernel:
        return ssd_scan(x, dt, A, B, C, chunk=chunk, interpret=interpret)
    return ssd_ref_chunked(x, dt, A, B, C, chunk=chunk)

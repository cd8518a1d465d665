"""Jit'd public wrapper for KV-Gen; dispatches kernel vs oracle."""
from __future__ import annotations

import jax

from repro.kernels.kv_gen.kernel import kv_gen
from repro.kernels.kv_gen.ref import kv_gen_ref


def kv_gen_pages(act_pages, norm_scale, wk, wv, *, norm_type="rmsnorm",
                 eps=1e-6, use_kernel=True, interpret=False):
    """Recompute (K, V) for a batch of 16-token ACT pages (paper Eq. 7).

    On TPU call with interpret=False; on CPU either interpret=True (kernel
    body validated in the Pallas interpreter) or use_kernel=False (XLA path).
    """
    if use_kernel:
        return kv_gen(act_pages, norm_scale, wk, wv, norm_type=norm_type,
                      eps=eps, interpret=interpret)
    return kv_gen_ref(act_pages, norm_scale, wk, wv, norm_type=norm_type, eps=eps)

"""Jit'd wrapper for the flash-attention kernel."""
from __future__ import annotations

from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref


def attention(q, k, v, *, causal=True, window=0, use_kernel=True,
              interpret=False, q_chunk=512, k_chunk=512):
    if use_kernel:
        return flash_attention(q, k, v, causal=causal, window=window,
                               q_chunk=q_chunk, k_chunk=k_chunk,
                               interpret=interpret)
    return flash_attention_ref(q, k, v, causal=causal, window=window)

"""Public wrapper for hybrid paged attention.

Kernel path (fused ACT->KV + attention) covers learned-positional models —
the paper's OPT family — where no positional transform applies at recompute
time.  RoPE architectures take the XLA path from models/model.py (the
hybrid_decode_step), which applies RoPE to recomputed keys; the kernel fusion
for RoPE is listed as future work in DESIGN.md.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.hybrid_attention.kernel import hybrid_paged_attention
from repro.kernels.hybrid_attention.ref import hybrid_paged_attention_ref


def paged_hybrid_attention(q, k_pages, v_pages, act_pages, norm_scale, wk, wv,
                           page_table, page_type, page_ntok, *,
                           use_kernel=True, interpret=False,
                           pages_bound=None, **kw):
    """pages_bound: static bound on any request's used-page count (the
    scheduler owns the page tables and knows it exactly); shrinks the
    kernel's page grid dimension below MAXP (DESIGN.md §7.4).  An
    insufficient bound would silently truncate attention, so it is checked
    here whenever the page_type table is concrete (the common eager case —
    inside a jit trace the caller's contract stands).

    Quantized pools: pass int8 pages plus ``k_scales``/``v_scales``/
    ``act_scales`` through ``**kw`` — both the kernel (on-tile dequant) and
    the reference (dense dequant up front) accept them (DESIGN.md §14)."""
    if pages_bound is not None and not isinstance(page_type, jax.core.Tracer):
        used = int(jnp.sum((page_type != 2).astype(jnp.int32), axis=1).max())
        if pages_bound < used:
            raise ValueError(
                f"pages_bound={pages_bound} < max used pages {used}: "
                "the kernel would drop context")
    if use_kernel:
        return hybrid_paged_attention(q, k_pages, v_pages, act_pages,
                                      norm_scale, wk, wv, page_table,
                                      page_type, page_ntok,
                                      interpret=interpret,
                                      pages_bound=pages_bound, **kw)
    return hybrid_paged_attention_ref(q, k_pages, v_pages, act_pages,
                                      norm_scale, wk, wv, page_table,
                                      page_type, page_ntok, **kw)

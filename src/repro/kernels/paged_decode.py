"""Paged flash-decode Pallas kernel (vLLM-style block-table indirection).

Serving engines fragment each request's KV cache into fixed-size PAGES drawn
from a shared pool — the free-list ``PageBlockAllocator`` in
``repro.serve.kv_cache``, whose per-request page tables
(``PagedKVManager.table_array``) are exactly the ``page_table`` operand
below; decode attention must then gather a request's pages via its block
table.  On TPU the indirection maps onto
**scalar-prefetched BlockSpec index_maps**: the page table lives in SMEM and
the grid's page step picks which pool page the next VMEM DMA fetches —
no gather materialization, the KV stream stays at HBM bandwidth.

Layout:
    q           [B, H, hd]             one query token per request, all heads
    k/v pool    [KV, n_pool, page, hd] the shared page pool, per kv head
    page_table  [B, max_pages] int32   pool index of each logical page
    seq_lens    [B] int32              valid tokens per request

Grid = (B, H, max_pages), page axis innermost/sequential; query head ``h``
reads kv head ``h // (H // KV)`` (GQA), so one ``[B, max_pages]`` table
serves every head and the SMEM it takes scales with B·max_pages, not
B·H·max_pages.  Online-softmax accumulators persist in VMEM scratch across
the page sweep.  Pages past a request's length are masked entirely (their
DMA is wasted but harmless; production tables sort requests by length to
trim the grid).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _sweep_page(q_ref, k, v, o_ref, acc_ref, m_ref, l_ref, *, seq_len,
                scale: float, page: int, n_pages: int):
    """One page step of the online softmax for one (request, head)."""
    pi = pl.program_id(2)

    @pl.when(pi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)  # [1, hd]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # [1, page]
    tok = pi * page + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
    s = jnp.where(tok < seq_len, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new

    @pl.when(pi == n_pages - 1)
    def _finalize():
        l = l_ref[...]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / safe).astype(o_ref.dtype)


def _paged_kernel(
    table_ref,  # scalar-prefetch: [B * max_pages] int32
    lens_ref,  # scalar-prefetch: [B] int32
    q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
    **kw,
):
    _sweep_page(
        q_ref,
        k_ref[0, 0].astype(jnp.float32),  # [page, hd]
        v_ref[0, 0].astype(jnp.float32),
        o_ref, acc_ref, m_ref, l_ref,
        seq_len=lens_ref[pl.program_id(0)], **kw,
    )


def _paged_kernel_int8(
    table_ref,  # scalar-prefetch: [B * max_pages] int32
    lens_ref,  # scalar-prefetch: [B] int32
    k_scale_ref,  # scalar-prefetch: [KV * n_pool] f32 per-page K scale
    v_scale_ref,  # scalar-prefetch: [KV * n_pool] f32 per-page V scale
    q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
    *, group: int, n_pool: int, n_pages: int, **kw,
):
    """int8-KV page sweep: pool pages are ``dist/compression.py`` codes
    (symmetric int8, amax/127 scale) and the dequant happens HERE, between
    the DMA and the dot — a page promoted from the compressed host tier
    never needs the separate dequantize/write-back pass ``tick_tiers``
    otherwise runs."""
    b = pl.program_id(0)
    row = (pl.program_id(1) // group) * n_pool + table_ref[
        b * n_pages + pl.program_id(2)
    ]
    _sweep_page(
        q_ref,
        k_ref[0, 0].astype(jnp.float32) * k_scale_ref[row],  # [page, hd]
        v_ref[0, 0].astype(jnp.float32) * v_scale_ref[row],
        o_ref, acc_ref, m_ref, l_ref,
        seq_len=lens_ref[b], n_pages=n_pages, **kw,
    )


def _paged_call(kernel, q, k_pool, v_pool, page_table, seq_lens, scales,
                *, name: str, interpret: bool):
    """Shared grid/BlockSpec wiring of the bf16/f32 and int8 kernels;
    ``scales`` are extra scalar-prefetch operands after table and lens.
    ``name`` names the kernel's custom call in HLO and in device traces."""
    b, h, hd = q.shape
    kv, _, page, _ = k_pool.shape
    group = h // kv
    n_pages = page_table.shape[1]

    def kv_map(bi, hi, pi, table, *_):
        return (hi // group, table[bi * n_pages + pi], 0, 0)

    def q_map(bi, hi, pi, *_):
        return (bi, hi, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2 + len(scales),
        grid=(b, h, n_pages),
        in_specs=[
            pl.BlockSpec((1, 1, 1, hd), q_map),
            # the indirection: the page axis fetches pool page table[b, pi]
            pl.BlockSpec((1, 1, page, hd), kv_map),
            pl.BlockSpec((1, 1, page, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, hd), q_map),
        scratch_shapes=[
            pltpu.VMEM((1, hd), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            kernel, scale=1.0 / math.sqrt(hd), page=page, n_pages=n_pages
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, 1, hd), q.dtype),
        interpret=interpret,
        name=name,
    )(page_table.astype(jnp.int32).reshape(-1), seq_lens.astype(jnp.int32),
      *scales, q[:, :, None, :], k_pool, v_pool)
    return out[:, :, 0, :]


def paged_decode_attention_int8(
    q: jax.Array,  # [B, H, hd]
    k_pool: jax.Array,  # [KV, n_pool, page, hd] int8 codes
    v_pool: jax.Array,  # [KV, n_pool, page, hd] int8 codes
    k_scales: jax.Array,  # [KV, n_pool] f32 per-page scale
    v_scales: jax.Array,  # [KV, n_pool] f32 per-page scale
    page_table: jax.Array,  # [B, max_pages] int32
    seq_lens: jax.Array,  # [B] int32
    *,
    interpret: bool = False,
) -> jax.Array:
    kv, n_pool = k_scales.shape
    kernel = functools.partial(
        _paged_kernel_int8, group=q.shape[1] // kv, n_pool=n_pool
    )
    scales = (k_scales.astype(jnp.float32).reshape(-1),
              v_scales.astype(jnp.float32).reshape(-1))
    return _paged_call(kernel, q, k_pool, v_pool, page_table, seq_lens,
                       scales, name="paged_decode_attention_int8",
                       interpret=interpret)


def paged_decode_attention(
    q: jax.Array,  # [B, H, hd]
    k_pool: jax.Array,  # [KV, n_pool, page, hd]
    v_pool: jax.Array,  # [KV, n_pool, page, hd]
    page_table: jax.Array,  # [B, max_pages] int32
    seq_lens: jax.Array,  # [B] int32
    *,
    interpret: bool = False,
) -> jax.Array:
    return _paged_call(_paged_kernel, q, k_pool, v_pool, page_table,
                       seq_lens, (), name="paged_decode_attention",
                       interpret=interpret)

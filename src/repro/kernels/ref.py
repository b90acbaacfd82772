"""Pure-jnp oracles for every Pallas kernel (the correctness reference)."""

from __future__ import annotations

import math
import jax
import jax.numpy as jnp

NEG_INF = -1e30


def flash_attention_ref(
    q: jax.Array,  # [BH, Sq, hd]
    k: jax.Array,  # [BH, Sk, hd]
    v: jax.Array,  # [BH, Sk, hd]
    *,
    causal: bool = True,
    window: int = 0,
    q_offset: int = 0,
) -> jax.Array:
    sq, sk = q.shape[1], k.shape[1]
    hd = q.shape[-1]
    scale = 1.0 / math.sqrt(hd)
    s = jnp.einsum(
        "bqd,bkd->bqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    q_pos = q_offset + jnp.arange(sq)[:, None]
    k_pos = jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask &= q_pos >= k_pos
    if window > 0:
        mask &= (q_pos - k_pos) < window
    s = jnp.where(mask[None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    # rows with no valid key → zero output (matches kernel's safe-divide)
    any_valid = mask.any(axis=-1)[None, :, None]
    out = jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32))
    out = jnp.where(any_valid, out, 0.0)
    return out.astype(q.dtype)


def decode_attention_ref(
    q: jax.Array,  # [BH, hd]
    k: jax.Array,  # [BH, S, hd]
    v: jax.Array,  # [BH, S, hd]
    cur_pos: int,  # attend to positions [0, cur_pos]
    *,
    window: int = 0,
) -> jax.Array:
    hd = q.shape[-1]
    scale = 1.0 / math.sqrt(hd)
    s = jnp.einsum(
        "bd,bkd->bk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    k_pos = jnp.arange(k.shape[1])
    mask = k_pos <= cur_pos
    if window > 0:
        mask &= k_pos > (cur_pos - window)
    s = jnp.where(mask[None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bk,bkd->bd", p, v.astype(jnp.float32)).astype(q.dtype)


def grouped_matmul_ref(
    x: jax.Array,  # [E, C, d]
    w: jax.Array,  # [E, d, f]
) -> jax.Array:
    return jnp.einsum(
        "ecd,edf->ecf", x.astype(jnp.float32), w.astype(jnp.float32)
    ).astype(x.dtype)


def ssd_scan_ref(
    x: jax.Array,  # [B, S, nh, hd]
    dt: jax.Array,  # [B, S, nh]  (f32, post-softplus)
    A: jax.Array,  # [nh]        (negative)
    Bm: jax.Array,  # [B, S, ds]
    C: jax.Array,  # [B, S, ds]
) -> jax.Array:
    """Sequential (non-chunked) SSD recurrence — the gold reference.

    h_t = exp(dt_t A) h_{t-1} + dt_t B_t ⊗ x_t;   y_t = C_t · h_t
    """
    b, s, nh, hd = x.shape
    ds = Bm.shape[-1]

    def step(h, inputs):
        xt, dtt, Bt, Ct = inputs  # [b,nh,hd], [b,nh], [b,ds], [b,ds]
        decay = jnp.exp(dtt * A[None, :])  # [b,nh]
        h = decay[:, :, None, None] * h + jnp.einsum(
            "bd,bhp->bhpd", Bt, dtt[..., None] * xt
        )
        y = jnp.einsum("bhpd,bd->bhp", h, Ct)
        return h, y

    h0 = jnp.zeros((b, nh, hd, ds), jnp.float32)
    xs = (
        x.transpose(1, 0, 2, 3).astype(jnp.float32),
        dt.transpose(1, 0, 2).astype(jnp.float32),
        Bm.transpose(1, 0, 2).astype(jnp.float32),
        C.transpose(1, 0, 2).astype(jnp.float32),
    )
    _, ys = jax.lax.scan(step, h0, xs)
    return ys.transpose(1, 0, 2, 3)  # [B, S, nh, hd] f32


def paged_decode_attention_ref(
    q: jax.Array,  # [B, H, hd]
    k_pool: jax.Array,  # [KV, n_pages, page, hd]
    v_pool: jax.Array,
    page_table: jax.Array,  # [B, max_pages]
    seq_lens: jax.Array,  # [B]
) -> jax.Array:
    """Gather-based oracle: materialize each request's KV then attend."""
    b, h, hd = q.shape
    kv, _, page, _ = k_pool.shape
    max_pages = page_table.shape[1]
    head_kv = jnp.arange(h) // (h // kv)  # GQA: q head → its kv head
    k = k_pool[:, page_table].reshape(kv, b, max_pages * page, hd)[head_kv]
    v = v_pool[:, page_table].reshape(kv, b, max_pages * page, hd)[head_kv]
    scale = 1.0 / math.sqrt(hd)
    s = jnp.einsum(
        "bhd,hbkd->bhk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    tok = jnp.arange(max_pages * page)[None, None, :]
    s = jnp.where(tok < seq_lens[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum(
        "bhk,hbkd->bhd", p, v.astype(jnp.float32)
    ).astype(q.dtype)


def paged_decode_attention_int8_ref(
    q: jax.Array,  # [B, H, hd]
    k_pool: jax.Array,  # [KV, n_pages, page, hd] int8 codes
    v_pool: jax.Array,
    k_scales: jax.Array,  # [KV, n_pages] f32
    v_scales: jax.Array,
    page_table: jax.Array,  # [B, max_pages]
    seq_lens: jax.Array,  # [B]
) -> jax.Array:
    """Dequantize the whole pool up front, then run the f32 oracle — the
    exact two-pass flow the in-kernel dequant is meant to eliminate."""
    k = k_pool.astype(jnp.float32) * k_scales[..., None, None]
    v = v_pool.astype(jnp.float32) * v_scales[..., None, None]
    return paged_decode_attention_ref(q, k, v, page_table, seq_lens)

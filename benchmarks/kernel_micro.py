"""Pallas kernel microbenchmarks: allclose vs oracle + wall time per call.

On this CPU container the kernels run in interpret mode, so the wall time
is the *interpreter's*, not the TPU's — correctness (max |err|) is the
meaningful column; the FLOPs-derived TPU-bound is reported alongside.
"""

import time

import jax
import jax.numpy as jnp

from repro.kernels import ops, ref
from .common import emit

PEAK = 197e12


def _time(fn, *args, reps=3, **kw):
    out = fn(*args, **kw)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args, **kw)
    jax.block_until_ready(out)
    return out, (time.perf_counter() - t0) / reps * 1e6  # µs


def _paged_decode_leg(key) -> dict:
    """Paged-decode sweep over (active batch × pages): the serving hot
    path's kernel at the shapes the engine actually batches.  Returns the
    rows recorded under BENCH_serve.json's ``kernels.paged_decode`` key."""
    rows = {}
    page, hd = 16, 64
    for bh, n_pages in ((1, 2), (4, 4), (8, 8), (16, 16)):
        ks = jax.random.split(key, 5)
        pool_pages = n_pages * 2  # pool larger than any one table
        k_pool = jax.random.normal(ks[0], (1, pool_pages, page, hd), jnp.bfloat16)
        v_pool = jax.random.normal(ks[1], (1, pool_pages, page, hd), jnp.bfloat16)
        q = jax.random.normal(ks[2], (bh, 1, hd), jnp.bfloat16)
        table = jax.random.randint(ks[3], (bh, n_pages), 0, pool_pages)
        lens = jax.random.randint(ks[4], (bh,), 1, n_pages * page + 1)
        out, us = _time(
            ops.paged_decode_attention, q, k_pool, v_pool, table, lens,
            reps=1,
        )
        gold = ref.paged_decode_attention_ref(q, k_pool, v_pool, table, lens)
        err = float(
            jnp.abs(out.astype(jnp.float32) - gold.astype(jnp.float32)).max()
        )
        label = f"b{bh}_p{n_pages}"
        emit(f"kernel.paged_decode.{label}.us_per_call", round(us, 1),
             f"interpret-mode; max_err={err:.4f}")
        rows[label] = {"us_per_call": round(us, 1), "max_err": err}
    return rows


def _paged_decode_int8_leg(key) -> dict:
    """int8-KV variant: per-page ``dist/compression`` codes dequantized
    inside the page sweep (the compressed host tier's promotion-free
    read path)."""
    from repro.dist.compression import quantize

    page, hd, bh, n_pages = 16, 64, 8, 8
    ks = jax.random.split(key, 5)
    pool_pages = n_pages * 2
    kf = jax.random.normal(ks[0], (1, pool_pages, page, hd), jnp.float32)
    vf = jax.random.normal(ks[1], (1, pool_pages, page, hd), jnp.float32)
    kq, ksc = jax.vmap(jax.vmap(quantize))(kf)
    vq, vsc = jax.vmap(jax.vmap(quantize))(vf)
    q = jax.random.normal(ks[2], (bh, 1, hd), jnp.float32)
    table = jax.random.randint(ks[3], (bh, n_pages), 0, pool_pages)
    lens = jax.random.randint(ks[4], (bh,), 1, n_pages * page + 1)
    out, us = _time(
        ops.paged_decode_attention_int8, q, kq, vq, ksc, vsc, table, lens,
        reps=1,
    )
    gold = ref.paged_decode_attention_int8_ref(
        q, kq, vq, ksc, vsc, table, lens
    )
    err = float(jnp.abs(out - gold).max())
    emit("kernel.paged_decode_int8.us_per_call", round(us, 1),
         f"interpret-mode; max_err={err:.5f} (vs dequantized oracle)")
    return {
        f"b{bh}_p{n_pages}": {"us_per_call": round(us, 1), "max_err": err}
    }


def main() -> dict:
    key = jax.random.PRNGKey(0)
    k1, k2, k3 = jax.random.split(key, 3)

    # flash attention
    BH, S, HD = 4, 512, 128
    q = jax.random.normal(k1, (BH, S, HD), jnp.bfloat16)
    k = jax.random.normal(k2, (BH, S, HD), jnp.bfloat16)
    v = jax.random.normal(k3, (BH, S, HD), jnp.bfloat16)
    out, us = _time(ops.flash_attention, q, k, v, causal=True, reps=1)
    gold = ref.flash_attention_ref(q, k, v, causal=True)
    err = float(jnp.abs(out.astype(jnp.float32) - gold.astype(jnp.float32)).max())
    flops = 4 * BH * S * S * HD * 0.5
    emit("kernel.flash_attention.us_per_call", round(us, 1),
         f"interpret-mode; max_err={err:.4f}; tpu_bound_us={flops / PEAK * 1e6:.2f}")

    # decode attention
    qd = jax.random.normal(k1, (BH, HD), jnp.bfloat16)
    out, us = _time(ops.decode_attention, qd, k, v, 300, reps=1)
    gold = ref.decode_attention_ref(qd, k, v, 300)
    err = float(jnp.abs(out.astype(jnp.float32) - gold.astype(jnp.float32)).max())
    emit("kernel.decode_attention.us_per_call", round(us, 1),
         f"interpret-mode; max_err={err:.4f}")

    # grouped matmul
    E, C, D, F = 8, 128, 512, 256
    x = jax.random.normal(k1, (E, C, D), jnp.bfloat16)
    w = jax.random.normal(k2, (E, D, F), jnp.bfloat16)
    out, us = _time(ops.grouped_matmul, x, w, reps=1)
    gold = ref.grouped_matmul_ref(x, w)
    rel = float(
        (jnp.abs(out.astype(jnp.float32) - gold.astype(jnp.float32)).max()
         / jnp.abs(gold.astype(jnp.float32)).max())
    )
    flops = 2 * E * C * D * F
    emit("kernel.grouped_matmul.us_per_call", round(us, 1),
         f"interpret-mode; rel_err={rel:.5f}; tpu_bound_us={flops / PEAK * 1e6:.2f}")

    # ssd scan
    B, S2, NH, HD2, DS = 2, 256, 4, 64, 32
    xs = jax.random.normal(k1, (B, S2, NH, HD2), jnp.float32) * 0.5
    dt = jax.nn.softplus(jax.random.normal(k2, (B, S2, NH), jnp.float32))
    A = -jnp.exp(jax.random.normal(k3, (NH,), jnp.float32) * 0.3)
    Bm = jax.random.normal(k1, (B, S2, DS), jnp.float32) * 0.5
    Cm = jax.random.normal(k2, (B, S2, DS), jnp.float32) * 0.5
    out, us = _time(ops.ssd_scan, xs, dt, A, Bm, Cm, chunk=64, reps=1)
    gold = ref.ssd_scan_ref(xs, dt, A, Bm, Cm)
    err = float(jnp.abs(out - gold).max())
    emit("kernel.ssd_scan.us_per_call", round(us, 1),
         f"interpret-mode; max_err={err:.5f}")

    # paged decode (the serving hot path) + its int8-KV variant: these
    # rows land in BENCH_serve.json under the "kernels" key
    return {
        "paged_decode": _paged_decode_leg(k2),
        "paged_decode_int8": _paged_decode_int8_leg(k3),
    }


if __name__ == "__main__":
    main()

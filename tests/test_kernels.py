"""Pallas kernel validation: hypothesis shape/dtype sweeps vs ref oracles.

All kernels run in interpret mode on CPU (the kernel body executes in
Python); assert_allclose against the pure-jnp oracle in ref.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels import ops, ref


def _rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


class TestFlashAttention:
    @pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
    @pytest.mark.parametrize("causal,window", [(True, 0), (True, 32), (False, 0)])
    def test_modes(self, dtype, causal, window):
        key = jax.random.PRNGKey(0)
        k1, k2, k3 = jax.random.split(key, 3)
        q = _rand(k1, (2, 128, 64), dtype)
        k = _rand(k2, (2, 128, 64), dtype)
        v = _rand(k3, (2, 128, 64), dtype)
        out = ops.flash_attention(
            q, k, v, causal=causal, window=window, block_q=64, block_k=64
        )
        gold = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
        np.testing.assert_allclose(
            np.asarray(out, np.float32),
            np.asarray(gold, np.float32),
            atol=2e-2 if dtype == jnp.bfloat16 else 2e-5,
        )

    @given(
        sq_blocks=st.integers(1, 4),
        sk_blocks=st.integers(1, 4),
        hd=st.sampled_from([32, 64, 128]),
        bh=st.integers(1, 3),
        q_offset=st.sampled_from([0, 64]),
    )
    @settings(max_examples=12, deadline=None)
    def test_shape_sweep(self, sq_blocks, sk_blocks, hd, bh, q_offset):
        key = jax.random.PRNGKey(sq_blocks * 100 + sk_blocks)
        k1, k2, k3 = jax.random.split(key, 3)
        sq, sk = sq_blocks * 64, sk_blocks * 64
        q = _rand(k1, (bh, sq, hd), jnp.float32)
        k = _rand(k2, (bh, sk, hd), jnp.float32)
        v = _rand(k3, (bh, sk, hd), jnp.float32)
        out = ops.flash_attention(
            q, k, v, causal=True, q_offset=q_offset, block_q=64, block_k=64
        )
        gold = ref.flash_attention_ref(q, k, v, causal=True, q_offset=q_offset)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(gold), atol=1e-4
        )


class TestDecodeAttention:
    @pytest.mark.parametrize("cur_pos", [0, 63, 100, 255])
    def test_positions(self, cur_pos):
        key = jax.random.PRNGKey(1)
        k1, k2, k3 = jax.random.split(key, 3)
        q = _rand(k1, (4, 64), jnp.float32)
        k = _rand(k2, (4, 256, 64), jnp.float32)
        v = _rand(k3, (4, 256, 64), jnp.float32)
        out = ops.decode_attention(q, k, v, cur_pos, block_k=64)
        gold = ref.decode_attention_ref(q, k, v, cur_pos)
        np.testing.assert_allclose(np.asarray(out), np.asarray(gold), atol=1e-4)

    def test_sliding_window(self):
        key = jax.random.PRNGKey(2)
        k1, k2, k3 = jax.random.split(key, 3)
        q = _rand(k1, (2, 32), jnp.float32)
        k = _rand(k2, (2, 128, 32), jnp.float32)
        v = _rand(k3, (2, 128, 32), jnp.float32)
        out = ops.decode_attention(q, k, v, 100, window=16, block_k=32)
        gold = ref.decode_attention_ref(q, k, v, 100, window=16)
        np.testing.assert_allclose(np.asarray(out), np.asarray(gold), atol=1e-4)


class TestGroupedMatmul:
    @given(
        e=st.integers(1, 6),
        c_blocks=st.integers(1, 3),
        d_blocks=st.integers(1, 3),
        f_blocks=st.integers(1, 2),
    )
    @settings(max_examples=10, deadline=None)
    def test_shape_sweep(self, e, c_blocks, d_blocks, f_blocks):
        key = jax.random.PRNGKey(e)
        k1, k2 = jax.random.split(key)
        c, d, f = c_blocks * 64, d_blocks * 128, f_blocks * 64
        x = _rand(k1, (e, c, d), jnp.float32)
        w = _rand(k2, (e, d, f), jnp.float32)
        out = ops.grouped_matmul(x, w, block_c=64, block_f=64, block_d=128)
        gold = ref.grouped_matmul_ref(x, w)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(gold), rtol=1e-4, atol=1e-3
        )

    def test_bf16(self):
        key = jax.random.PRNGKey(7)
        k1, k2 = jax.random.split(key)
        x = _rand(k1, (4, 128, 256), jnp.bfloat16)
        w = _rand(k2, (4, 256, 128), jnp.bfloat16)
        out = ops.grouped_matmul(x, w, block_d=128)
        gold = ref.grouped_matmul_ref(x, w)
        rel = np.abs(
            np.asarray(out, np.float32) - np.asarray(gold, np.float32)
        ).max() / max(np.abs(np.asarray(gold, np.float32)).max(), 1e-9)
        assert rel < 2e-2


class TestSSDScan:
    @given(
        chunks=st.integers(1, 4),
        nh=st.integers(1, 4),
        hd=st.sampled_from([16, 32]),
        ds=st.sampled_from([8, 16]),
    )
    @settings(max_examples=10, deadline=None)
    def test_shape_sweep(self, chunks, nh, hd, ds):
        key = jax.random.PRNGKey(chunks * 10 + nh)
        k1, k2, k3 = jax.random.split(key, 3)
        b, s = 2, chunks * 32
        x = _rand(k1, (b, s, nh, hd), jnp.float32) * 0.5
        dt = jax.nn.softplus(_rand(k2, (b, s, nh), jnp.float32))
        A = -jnp.exp(_rand(k3, (nh,), jnp.float32) * 0.3)
        Bm = _rand(k1, (b, s, ds), jnp.float32) * 0.5
        C = _rand(k2, (b, s, ds), jnp.float32) * 0.5
        out = ops.ssd_scan(x, dt, A, Bm, C, chunk=32)
        gold = ref.ssd_scan_ref(x, dt, A, Bm, C)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(gold), rtol=1e-3, atol=1e-3
        )

    def test_matches_model_chunked_scan(self):
        """The Pallas kernel, the model's jnp chunked scan, and the
        sequential recurrence must all agree."""
        from repro.models.blocks import _ssd_scan

        key = jax.random.PRNGKey(3)
        k1, k2, k3 = jax.random.split(key, 3)
        b, s, nh, hd, ds = 2, 96, 2, 16, 8
        x = _rand(k1, (b, s, nh, hd), jnp.float32) * 0.5
        dt = jax.nn.softplus(_rand(k2, (b, s, nh), jnp.float32))
        A = -jnp.exp(_rand(k3, (nh,), jnp.float32) * 0.3)
        Bm = _rand(k1, (b, s, ds), jnp.float32) * 0.5
        C = _rand(k2, (b, s, ds), jnp.float32) * 0.5
        gold = ref.ssd_scan_ref(x, dt, A, Bm, C)
        model = _ssd_scan(x, dt, A, Bm, C, chunk=32)
        kern = ops.ssd_scan(x, dt, A, Bm, C, chunk=32)
        np.testing.assert_allclose(np.asarray(model), np.asarray(gold), atol=1e-3)
        np.testing.assert_allclose(np.asarray(kern), np.asarray(gold), atol=1e-3)


class TestPagedDecode:
    @given(
        b=st.integers(1, 3),
        heads=st.sampled_from([(1, 1), (2, 1), (4, 2)]),  # (H, KV): GQA
        max_pages=st.integers(1, 4),
        page=st.sampled_from([16, 32]),
        hd=st.sampled_from([32, 64]),
        seed=st.integers(0, 100),
    )
    @settings(max_examples=12, deadline=None)
    def test_matches_gather_oracle(self, b, heads, max_pages, page, hd, seed):
        h, kv = heads
        key = jax.random.PRNGKey(seed)
        k1, k2, k3, k4, k5 = jax.random.split(key, 5)
        n_pool = b * max_pages + 3
        q = _rand(k1, (b, h, hd), jnp.float32)
        k_pool = _rand(k2, (kv, n_pool, page, hd), jnp.float32)
        v_pool = _rand(k3, (kv, n_pool, page, hd), jnp.float32)
        # random non-overlapping-ish page table + random valid lengths ≥ 1
        table = jax.random.permutation(k4, n_pool)[: b * max_pages].reshape(
            b, max_pages
        )
        lens = jax.random.randint(k5, (b,), 1, max_pages * page + 1)
        out = ops.paged_decode_attention(q, k_pool, v_pool, table, lens)
        gold = ref.paged_decode_attention_ref(q, k_pool, v_pool, table, lens)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(gold), atol=1e-4
        )

    def test_consumes_block_allocator_tables(self):
        """End-to-end: page tables produced by the serving block allocator
        drive the Pallas kernel; numerics must match the dense reference
        over each request's contiguous K/V."""
        from repro.configs import ARCHS
        from repro.serve.kv_cache import PagedKVManager, kv_bytes_per_token

        cfg = ARCHS["internlm2-1.8b"]
        page, hd = 16, 64
        page_bytes = kv_bytes_per_token(cfg) * page
        mgr = PagedKVManager(capacity_bytes=page_bytes * 8, page_tokens=page)
        lens = {"a": 40, "b": 17, "c": 60}  # c overflows the 8-page pool
        for rid, n in lens.items():
            mgr.register(rid, cfg)
            mgr.grow_to(rid, n)
        assert mgr.overflow_pages > 0  # the pool is genuinely overcommitted
        tables = {rid: mgr.page_table(rid) for rid in lens}
        flat = [pid for t in tables.values() for pid in t]
        assert len(set(flat)) == len(flat), "pages must never be shared"
        n_pool = mgr.page_id_bound  # ids are recycled; bound > current count
        key = jax.random.PRNGKey(0)
        q = jax.random.normal(key, (3, hd), jnp.float32)
        k_pool = np.zeros((n_pool, page, hd), np.float32)
        v_pool = np.zeros_like(k_pool)
        dense_k, dense_v = {}, {}
        for i, (rid, n) in enumerate(lens.items()):
            kk = jax.random.normal(jax.random.PRNGKey(10 + i),
                                   (len(tables[rid]) * page, hd))
            vv = jax.random.normal(jax.random.PRNGKey(20 + i),
                                   (len(tables[rid]) * page, hd))
            dense_k[rid], dense_v[rid] = np.asarray(kk), np.asarray(vv)
            for j, pid in enumerate(tables[rid]):
                k_pool[pid] = dense_k[rid][j * page:(j + 1) * page]
                v_pool[pid] = dense_v[rid][j * page:(j + 1) * page]
        table = jnp.asarray(mgr.table_array(list(lens), max_pages=4))
        seq = jnp.asarray([lens[r] for r in lens], jnp.int32)
        out = np.asarray(
            ops.paged_decode_attention(
                q[:, None], jnp.asarray(k_pool)[None],
                jnp.asarray(v_pool)[None], table, seq,
            )
        )[:, 0]
        # dense per-request oracle: softmax over the contiguous K/V prefix
        for i, (rid, n) in enumerate(lens.items()):
            kk = dense_k[rid][:n]
            vv = dense_v[rid][:n]
            s = np.asarray(q)[i] @ kk.T / np.sqrt(hd)
            p = np.exp(s - s.max())
            p /= p.sum()
            np.testing.assert_allclose(out[i], p @ vv, atol=1e-4)

    def test_shared_prefix_pages_numerics(self):
        """Prefix sharing is a PAGE-TABLE property: requests whose tables
        alias the same prefix pages must read identical K/V through the
        kernel's indirection — no new kernel needed.  The tables come from
        a real prefix-cache match plus the engine's copy-on-write guard
        (the shared terminal page splits before request b's first write),
        and numerics are checked against per-request dense oracles and a
        physically-duplicated (no aliasing) layout."""
        from repro.configs import ARCHS
        from repro.serve.kv_cache import PagedKVManager, kv_bytes_per_token

        cfg = ARCHS["internlm2-1.8b"]
        page, hd = 16, 64
        page_bytes = kv_bytes_per_token(cfg) * page
        mgr = PagedKVManager(
            capacity_bytes=page_bytes * 16,
            page_tokens=page,
            enable_prefix_cache=True,
        )
        shared_prompt = list(range(40))  # 2 full pages + 8-token terminal
        mgr.register("a", cfg)
        mgr.grow_to("a", 64)  # prompt + decoded tokens: 4 pages
        mgr.insert_prefix("a", shared_prompt, "T", tuple(shared_prompt))
        mgr.register("b", cfg)
        matched, _ = mgr.match_prefix("b", shared_prompt)
        assert matched == 40
        # the engine's COW guard before b writes position 40 (which lands
        # in the shared terminal page): b gets a private copy
        mgr.make_private("b", 2)
        mgr.grow_to("b", 64)
        ta, tb = mgr.page_table("a"), mgr.page_table("b")
        assert ta[:2] == tb[:2], "full prefix pages must alias, not copy"
        assert not set(ta[2:]) & set(tb[2:]), "suffix pages must be private"

        # per-request dense K/V streams sharing the first 40 positions
        n_pool = mgr.page_id_bound
        q = jax.random.normal(jax.random.PRNGKey(3), (2, hd), jnp.float32)
        sa_k = np.asarray(jax.random.normal(jax.random.PRNGKey(11), (64, hd)))
        sa_v = np.asarray(jax.random.normal(jax.random.PRNGKey(12), (64, hd)))
        sb_k = np.concatenate(
            [sa_k[:40],
             np.asarray(jax.random.normal(jax.random.PRNGKey(13), (24, hd)))]
        )
        sb_v = np.concatenate(
            [sa_v[:40],
             np.asarray(jax.random.normal(jax.random.PRNGKey(14), (24, hd)))]
        )
        k_pool = np.zeros((n_pool, page, hd), np.float32)
        v_pool = np.zeros_like(k_pool)
        for table_ids, sk, sv in ((ta, sa_k, sa_v), (tb, sb_k, sb_v)):
            for j, pid in enumerate(table_ids):
                k_pool[pid] = sk[j * page:(j + 1) * page]
                v_pool[pid] = sv[j * page:(j + 1) * page]
        table = jnp.asarray(mgr.table_array(["a", "b"], max_pages=4))
        lens = jnp.asarray([50, 46], jnp.int32)
        out = np.asarray(
            ops.paged_decode_attention(
                q[:, None], jnp.asarray(k_pool)[None],
                jnp.asarray(v_pool)[None], table, lens,
            )
        )[:, 0]
        # oracle 1: dense per-request softmax over the contiguous prefix
        for i, (sk, sv, n) in enumerate(((sa_k, sa_v, 50), (sb_k, sb_v, 46))):
            s = np.asarray(q)[i] @ sk[:n].T / np.sqrt(hd)
            p = np.exp(s - s.max())
            p /= p.sum()
            np.testing.assert_allclose(out[i], p @ sv[:n], atol=1e-4)
        # oracle 2: physically duplicate b's shared pages into fresh pool
        # slots — aliased and duplicated layouts must agree exactly
        k2 = np.concatenate([k_pool, k_pool[np.asarray(ta[:2])]], axis=0)
        v2 = np.concatenate([v_pool, v_pool[np.asarray(ta[:2])]], axis=0)
        table_dup = np.asarray(table).copy()
        table_dup[1, :2] = np.arange(n_pool, n_pool + 2)
        out_dup = np.asarray(
            ops.paged_decode_attention(
                q[:, None], jnp.asarray(k2)[None], jnp.asarray(v2)[None],
                jnp.asarray(table_dup), lens,
            )
        )[:, 0]
        np.testing.assert_allclose(out, out_dup, atol=1e-6)


class TestPagedDecodeInt8:
    @given(
        b=st.integers(1, 3),
        heads=st.sampled_from([(1, 1), (2, 1), (4, 2)]),  # (H, KV): GQA
        max_pages=st.integers(1, 3),
        seed=st.integers(0, 50),
    )
    @settings(max_examples=8, deadline=None)
    def test_matches_dequantize_first_oracle(self, b, heads, max_pages, seed):
        """Dequantizing per-page int8 codes INSIDE the page sweep must
        match dequantizing the whole pool up front."""
        from repro.dist.compression import quantize

        h, kv = heads
        page, hd = 16, 64
        key = jax.random.PRNGKey(seed)
        k1, k2, k3, k4, k5 = jax.random.split(key, 5)
        n_pool = b * max_pages + 2
        q = _rand(k1, (b, h, hd), jnp.float32)
        kf = _rand(k2, (kv, n_pool, page, hd), jnp.float32)
        vf = _rand(k3, (kv, n_pool, page, hd), jnp.float32)
        kq, ks = jax.vmap(jax.vmap(quantize))(kf)
        vq, vs = jax.vmap(jax.vmap(quantize))(vf)
        table = jax.random.permutation(k4, n_pool)[: b * max_pages].reshape(
            b, max_pages
        )
        lens = jax.random.randint(k5, (b,), 1, max_pages * page + 1)
        out = ops.paged_decode_attention_int8(
            q, kq, vq, ks, vs, table, lens
        )
        gold = ref.paged_decode_attention_int8_ref(
            q, kq, vq, ks, vs, table, lens
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(gold), atol=1e-4
        )

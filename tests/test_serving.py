"""Serving engine tests: MURS HBM-admission vs FAIR under pressure."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.sched import MursConfig
from repro.models import (
    decode_step,
    init_cache,
    init_model,
    prefill_chunk,
    prefill_chunk_supported,
)
from repro.serve import EngineConfig, Request, ServingEngine
from repro.serve.kv_cache import (
    PageBlockAllocator,
    PagedKVManager,
    constant_state_bytes,
    kv_bytes_per_token,
)


@pytest.fixture(scope="module")
def small_model():
    cfg = ARCHS["internlm2-1.8b"].smoke()
    params = init_model(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _requests():
    reqs = [Request(f"A{i}", "A", list(range(10, 18)), 40) for i in range(3)]
    reqs += [Request(f"B{i}", "B", list(range(30, 34)), 6) for i in range(4)]
    return reqs


class TestKVManager:
    def test_byte_model_matches_murs_classes(self):
        """The per-arch marginal KV bytes realize the MURS memory models."""
        per_tok = {a: kv_bytes_per_token(ARCHS[a]) for a in ARCHS}
        # mamba2 decode is constant-model: zero marginal bytes
        assert per_tok["mamba2-2.7b"] == 0.0
        # MLA's latent cache is ~57× shallower than its own hypothetical
        # per-head K/V (128 heads × 2 × 128 dims vs kv_lora 512 + rope 64)
        dsv2 = ARCHS["deepseek-v2-236b"]
        per_head_kv = 2 * dsv2.n_kv_heads * dsv2.head_dim * 2 * dsv2.n_layers
        assert per_tok["deepseek-v2-236b"] < 0.05 * per_head_kv
        # mamba has constant state instead
        assert constant_state_bytes(ARCHS["mamba2-2.7b"]) > 0

    def test_paging_accounting(self):
        cfg = ARCHS["internlm2-1.8b"]
        mgr = PagedKVManager(capacity_bytes=1e9, page_tokens=16)
        mgr.register("r1", cfg)
        grew = mgr.grow_to("r1", 17)  # needs 2 pages
        assert grew == pytest.approx(2 * 16 * kv_bytes_per_token(cfg))
        assert mgr.grow_to("r1", 20) == 0.0  # still within 2 pages
        assert mgr.page_table("r1") == (0, 1)
        freed = mgr.release("r1")
        assert freed >= grew
        assert mgr.used_bytes == 0.0
        assert mgr.free_pages == mgr.n_pages


class TestPageBlockAllocator:
    def test_free_list_alloc_and_reuse(self):
        a = PageBlockAllocator(n_pages=4)
        assert a.grow_to("r1", 2) == 2
        assert a.table("r1") == (0, 1)
        assert a.grow_to("r2", 2) == 2
        assert a.table("r2") == (2, 3)
        assert a.free_pages == 0
        a.free("r1")
        assert a.free_pages == 2
        # LIFO reuse: the most recently freed pages come back first
        a.grow_to("r3", 1)
        assert a.table("r3")[0] in (0, 1)

    def test_overflow_pages_and_residency(self):
        a = PageBlockAllocator(n_pages=2)
        a.grow_to("r1", 2)
        assert a.resident("r1")
        a.grow_to("r2", 2)  # pool exhausted → overflow ids
        assert not a.resident("r2")
        assert a.overflow_pages == 2
        assert all(pid >= a.n_pages for pid in a.table("r2"))

    def test_reclaim_pages_overflow_back_in(self):
        a = PageBlockAllocator(n_pages=2)
        a.grow_to("r1", 2)
        a.grow_to("r2", 2)
        a.free("r1")
        moved = a.reclaim()
        assert moved == 2
        assert a.resident("r2")
        assert a.overflow_pages == 0
        assert all(pid < a.n_pages for pid in a.table("r2"))

    def test_table_array_pads_and_bounds(self):
        import numpy as np

        a = PageBlockAllocator(n_pages=8)
        a.grow_to("r1", 3)
        a.grow_to("r2", 1)
        arr = a.table_array(["r1", "r2"], max_pages=4)
        assert arr.shape == (2, 4) and arr.dtype == np.int32
        assert list(arr[0][:3]) == list(a.table("r1"))
        with pytest.raises(ValueError):
            a.table_array(["r1"], max_pages=2)


class TestChunkedPrefill:
    @pytest.mark.parametrize("arch", ["internlm2-1.8b", "mamba2-2.7b"])
    def test_chunked_matches_monolithic_greedy(self, small_model, arch):
        """A long prompt split into chunks must generate the same greedy
        tokens as a monolithic prefill, and a co-resident short request
        must keep decoding while the long prompt chunks through.

        internlm2 (plain attention, dense FFN) chunks through the batched
        multi-token forward; mamba2 through the per-token decode scan,
        whose recurrence is a different computation from its monolithic
        prefill, so only the run's completion and the path are checked."""
        if arch == "internlm2-1.8b":
            cfg, params = small_model
        else:
            cfg = ARCHS[arch].smoke()
            params = init_model(cfg, jax.random.PRNGKey(0))
        batched = arch == "internlm2-1.8b"
        prompt = list(range(5, 25))  # 20 tokens
        outs = {}
        for name, chunk in (("mono", 1000), ("chunk", 6)):
            eng = ServingEngine(
                cfg, params,
                EngineConfig(n_slots=2, max_seq=64, hbm_capacity_bytes=1e12,
                             prefill_chunk_tokens=chunk),
            )
            # short FIRST: it finishes its prefill in tick 0 and then
            # decodes on every tick the long prompt is still chunking —
            # the decode batch genuinely overlaps an in-flight prefill
            eng.submit(Request("short", "U", list(range(3, 7)), 8))
            eng.submit(Request("long", "T", prompt, 8))
            out = eng.run(max_ticks=100).extras
            assert sorted(eng.completed) == ["long", "short"]
            outs[name] = (
                eng.requests["long"].generated,
                eng.requests["short"].generated,
                out["chunked_prefill_ticks"],
                eng.requests["short"].finish_tick
                < eng.requests["long"].finish_tick,
                out["chunk_prefill_calls"],
                out["chunk_scan_calls"],
            )
        if batched:
            assert outs["mono"][0] == outs["chunk"][0]
            assert outs["mono"][1] == outs["chunk"][1]
        assert outs["chunk"][2] > 0 and outs["mono"][2] == 0
        assert outs["chunk"][3], "short request must finish during/ahead"
        calls = outs["chunk"][4:] if batched else outs["chunk"][4:][::-1]
        assert calls[0] > 0 and calls[1] == 0
        assert outs["mono"][4:] == (0, 0)


class TestChunkForwardParity:
    """``prefill_chunk`` (one ``[1, w]`` forward over the slot's cache)
    against the per-token ``decode_step`` scan it replaces."""

    N_SLOTS, MAX_SEQ, SLOT = 3, 64, 1

    @pytest.fixture(scope="class")
    def setup(self, small_model):
        cfg, params = small_model
        # a cache full of noise: rows the chunk must not touch are visible
        caches = jax.tree_util.tree_map(
            lambda x: jax.random.normal(
                jax.random.PRNGKey(1), x.shape, jnp.float32
            ).astype(x.dtype),
            init_cache(cfg, self.N_SLOTS, self.MAX_SEQ),
        )
        chunk = jax.jit(
            lambda t, c, s, p: prefill_chunk(cfg, params, t, c, s, p)
        )
        step = jax.jit(
            lambda t, c, p: decode_step(cfg, params, t[None, None], c, p)
        )
        return cfg, caches, chunk, step

    def _scan(self, step, tokens, caches, pos0):
        """The reference: the slot's cache as a batch of one, one
        decode_step per token."""
        sub = {
            "unit": jax.tree_util.tree_map(
                lambda x: x[:, self.SLOT:self.SLOT + 1], caches["unit"]
            ),
            "suffix": [],
        }
        for i, tok in enumerate(tokens):
            logits, sub = step(jnp.int32(tok), sub, jnp.int32(pos0 + i))
        return logits[0, 0], sub["unit"]

    @pytest.mark.parametrize("pos0", [0, 5, 17])
    @pytest.mark.parametrize("w", [1, 4, 8])
    def test_matches_per_token_scan(self, setup, w, pos0):
        self._check(setup, w, pos0)

    def test_one_token_at_the_last_row(self, setup):
        """A one-token chunk writes a two-row window; at the cache's last
        row that window starts one row early."""
        self._check(setup, 1, self.MAX_SEQ - 1)

    def _check(self, setup, w, pos0):
        cfg, caches, chunk, step = setup
        assert prefill_chunk_supported(cfg) and not cfg.suffix_blocks
        tokens = list(range(7, 7 + w))
        ref_logits, ref_unit = self._scan(step, tokens, caches, pos0)
        logits, out = chunk(
            jnp.asarray(tokens, jnp.int32), caches,
            jnp.int32(self.SLOT), jnp.int32(pos0),
        )
        np.testing.assert_allclose(
            np.asarray(logits), np.asarray(ref_logits), rtol=2e-2, atol=2e-2
        )
        rows = slice(pos0, pos0 + w)
        for new, ref, old in zip(
            jax.tree_util.tree_leaves(out["unit"]),
            jax.tree_util.tree_leaves(ref_unit),
            jax.tree_util.tree_leaves(caches["unit"]),
        ):
            new = np.asarray(new.astype(jnp.float32))
            old = np.asarray(old.astype(jnp.float32))
            # [layers, slots, KV, seq, hd]: the chunk's rows as the scan
            # wrote them, within bf16 rounding
            np.testing.assert_allclose(
                new[:, self.SLOT, :, rows],
                np.asarray(ref.astype(jnp.float32))[:, 0, :, rows],
                rtol=2e-2, atol=2e-2,
            )
            # every other row of the slot, and every other slot: the bits
            # it had
            mine = np.delete(new[:, self.SLOT], np.arange(pos0, pos0 + w), 2)
            was = np.delete(old[:, self.SLOT], np.arange(pos0, pos0 + w), 2)
            np.testing.assert_array_equal(mine, was)
            np.testing.assert_array_equal(
                np.delete(new, self.SLOT, 1), np.delete(old, self.SLOT, 1)
            )

    def test_stack_predicate(self):
        """Plain full attention with a dense FFN takes the batched path;
        MoE, Mamba, MLA, rings and enc-dec keep the per-token scan."""
        assert prefill_chunk_supported(ARCHS["internlm2-1.8b"].smoke())
        for arch in ("granite-moe-3b-a800m", "mamba2-2.7b",
                     "deepseek-v2-236b", "gemma3-1b", "whisper-base",
                     "zamba2-1.2b"):
            assert not prefill_chunk_supported(ARCHS[arch].smoke()), arch


class TestAdmissionLiveness:
    def test_impossible_prompt_fails_fast(self, small_model):
        """A prompt that can never fit the pool must fail at admission
        (OOM semantics) instead of head-of-line blocking the queue."""
        cfg, params = small_model
        cap = kv_bytes_per_token(cfg) * 32  # 2-page pool
        eng = ServingEngine(
            cfg, params,
            EngineConfig(n_slots=2, max_seq=64, hbm_capacity_bytes=cap),
        )
        eng.submit(Request("huge", "T", list(range(60)), 4))  # 4 pages > pool
        eng.submit(Request("ok", "U", list(range(4)), 4))
        eng.run(max_ticks=200)
        assert eng.requests["huge"].state == "failed"
        assert eng.requests["ok"].state == "done"


class TestEngineUnderPressure:
    @pytest.fixture(scope="class")
    def results(self, small_model):
        cfg, params = small_model
        cap = kv_bytes_per_token(cfg) * 80
        out = {}
        for mode, sched in (("fair", None), ("murs", MursConfig(period=1.0))):
            eng = ServingEngine(
                cfg, params,
                EngineConfig(n_slots=4, max_seq=64,
                             hbm_capacity_bytes=cap, scheduler=sched),
            )
            for r in _requests():
                eng.submit(r)
            out[mode] = eng.run(max_ticks=400).extras
        return out

    def test_fair_spills_under_pressure(self, results):
        """Stock scheduling pays in KV offloads (the TPU 'spill')."""
        assert results["fair"]["offload_events"] > 0

    def test_murs_avoids_spills_entirely(self, results):
        """Paper Table III: MURS reduces spills ~90%; here to zero."""
        assert results["murs"]["offload_events"] == 0

    def test_murs_completes_everything(self, results):
        """Paper §VI-C: MURS keeps serving where the baseline OOMs."""
        assert results["murs"]["failed"] == 0
        assert results["murs"]["completed"] == 7

    def test_murs_uses_suspension(self, results):
        assert results["murs"]["suspensions"] > 0

    def test_fair_hard_fails_when_offload_unavailable(self, small_model):
        """With no spill path (offload disabled), the stock scheduler throws
        the OOM analogue and fails requests; MURS still completes all —
        the paper's Fig 5 OME scenario."""
        cfg, params = small_model
        cap = kv_bytes_per_token(cfg) * 80
        out = {}
        for mode, sched in (("fair", None), ("murs", MursConfig(period=1.0))):
            eng = ServingEngine(
                cfg, params,
                EngineConfig(n_slots=4, max_seq=64, hbm_capacity_bytes=cap,
                             scheduler=sched, offload_enabled=False),
            )
            for r in _requests():
                eng.submit(r)
            out[mode] = eng.run(max_ticks=400).extras
        assert out["fair"]["failed"] > 0
        assert out["murs"]["failed"] == 0
        assert out["murs"]["completed"] == 7

    def test_no_pressure_no_interference(self, small_model):
        """With ample capacity MURS must not suspend anything."""
        cfg, params = small_model
        cap = kv_bytes_per_token(cfg) * 100000
        eng = ServingEngine(
            cfg, params,
            EngineConfig(n_slots=4, max_seq=64, hbm_capacity_bytes=cap,
                         scheduler=MursConfig(period=1.0)),
        )
        for r in _requests():
            eng.submit(r)
        out = eng.run(max_ticks=400).extras
        assert out["failed"] == 0
        assert out["suspensions"] == 0
        assert out["completed"] == 7


class TestDecodedTokensMatchUnbatchedReference(object):
    def test_engine_decode_matches_direct_decode(self, small_model):
        """Slot-batched engine decode must equal a direct single-request
        prefill+decode loop (greedy tokens identical)."""
        cfg, params = small_model
        from repro.models import decode_step, prefill

        prompt = list(range(10, 18))
        gen = 6
        # direct reference
        tokens = jnp.asarray(prompt, jnp.int32)[None]
        logits, caches = prefill(cfg, params, tokens, max_seq=64, remat=False)
        out_ref = [int(jnp.argmax(logits[0, -1]))]
        pos = len(prompt)
        for _ in range(gen - 1):
            logits, caches = decode_step(
                cfg, params,
                jnp.asarray([[out_ref[-1]]], jnp.int32), caches,
                jnp.int32(pos),
            )
            out_ref.append(int(jnp.argmax(logits[0, 0])))
            pos += 1
        # engine
        eng = ServingEngine(
            cfg, params,
            EngineConfig(n_slots=2, max_seq=64, hbm_capacity_bytes=1e12),
        )
        eng.submit(Request("r", "T", prompt, gen))
        eng.run(max_ticks=100)
        assert eng.requests["r"].generated[:gen] == out_ref


class TestMetricPopulations:
    def test_ttft_counts_only_completed_requests(self, small_model):
        """Regression: ttft_ticks used to include failed requests (any
        first_token_tick >= 0) while latency_ticks counted only
        state == "done" — under shedding the two percentile populations
        silently diverged.  Both now describe completed requests;
        failed-request TTFT is reported separately."""
        cfg, params = small_model
        eng = ServingEngine(
            cfg, params,
            EngineConfig(n_slots=2, max_seq=64, hbm_capacity_bytes=1e12),
        )
        eng.submit(Request("ok", "T", list(range(4)), 4))
        out = eng.run(max_ticks=100).extras
        assert len(out["ttft_ticks"]) == 1
        assert out["ttft_failed_ticks"] == []
        # a request that produced a first token and then failed must land
        # in the failed population, not the SLO one
        shed = Request("shed", "T", [1, 2], 4, submit_tick=0)
        shed.state = "failed"
        shed.first_token_tick = 7
        eng.requests["shed"] = shed
        out = eng.run(max_ticks=eng.tick).extras
        assert len(out["ttft_ticks"]) == 1
        assert out["ttft_failed_ticks"] == [7]
        assert len(out["ttft_ticks"]) == len(out["latency_ticks"])


class TestMemoryModelClassification:
    def test_decode_classifies_per_murs_models(self, small_model):
        """§III live: attention decodes classify LINEAR (KV grows per
        token); the classification is measured online by the sampler."""
        cfg, params = small_model
        cap = kv_bytes_per_token(cfg) * 1e6  # no pressure needed here
        eng = ServingEngine(
            cfg, params,
            EngineConfig(n_slots=2, max_seq=64, hbm_capacity_bytes=cap,
                         scheduler=MursConfig(period=1.0)),
        )
        eng.submit(Request("r", "T", list(range(8)), 20))
        out = eng.run(max_ticks=200).extras
        assert out["memory_models"]["r"] == "linear"

    def test_fair_offloads_murs_avoids(self, small_model):
        """Table III live analogue: the stock scheduler spills (offloads KV
        to host) under pressure; MURS suspension avoids it."""
        cfg, params = small_model
        cap = kv_bytes_per_token(cfg) * 90
        counts = {}
        for mode, sched in (("fair", None), ("murs", MursConfig(period=1.0))):
            eng = ServingEngine(
                cfg, params,
                EngineConfig(n_slots=4, max_seq=64, hbm_capacity_bytes=cap,
                             scheduler=sched),
            )
            reqs = [Request(f"A{i}", "A", list(range(10, 18)), 30)
                    for i in range(3)]
            reqs += [Request(f"B{i}", "B", list(range(30, 34)), 6)
                     for i in range(2)]
            for r in reqs:
                eng.submit(r)
            out = eng.run(max_ticks=600).extras
            counts[mode] = out
        assert counts["fair"]["offload_events"] > 0
        assert (
            counts["murs"]["offload_events"]
            < counts["fair"]["offload_events"]
        )

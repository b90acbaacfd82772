"""Entry points: the serving launcher's ``build_engine``, the compile-cache
placement, and ``chip_smoke.py``'s refusal to run anywhere but a TPU."""

import importlib.util
from pathlib import Path

import jax
import pytest

import repro.launch.serve as serve_launcher
from repro.launch import REPO_ROOT, enable_compile_cache
from repro.serve.kv_cache import kv_bytes_per_token

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestChipSmoke:
    def test_refuses_non_tpu_before_building(self, chip_smoke, monkeypatch,
                                             capsys):
        assert jax.devices()[0].platform != "tpu"

        def no_build(*_a, **_kw):
            raise AssertionError("built a model without a TPU")

        monkeypatch.setattr(serve_launcher, "build_engine", no_build)
        assert chip_smoke.main() != 0
        out = capsys.readouterr()
        assert '"ok"' not in out.out
        assert "no TPU" in out.err

    def test_paged_vs_dense_within_tolerance_at_smoke_size(self, chip_smoke):
        engine = serve_launcher.build_engine(
            chip_smoke.ARCH, full=False, slots=2, max_seq=64
        )
        diff, tol = chip_smoke.paged_vs_dense(engine)
        assert 0.0 < tol and diff <= tol


class TestBuildEngine:
    def test_pool_defaults_to_the_dense_caches(self):
        engine = serve_launcher.build_engine(
            "internlm2-1.8b", full=False, slots=3, max_seq=32
        )
        per_token = kv_bytes_per_token(engine.cfg)
        assert engine.ecfg.hbm_capacity_bytes == 3 * 32 * per_token
        assert engine.ecfg.n_slots == 3 and engine.ecfg.max_seq == 32

    def test_smoke_unless_full(self):
        engine = serve_launcher.build_engine(
            "internlm2-1.8b", full=False, slots=1, max_seq=16, pool_tokens=80
        )
        assert engine.cfg.d_model == 64  # the smoke config
        per_token = max(kv_bytes_per_token(engine.cfg), 1.0)
        assert engine.ecfg.hbm_capacity_bytes == 80 * per_token


class TestCompileCache:
    @pytest.fixture
    def cache_config(self):
        before = jax.config.jax_compilation_cache_dir
        yield
        jax.config.update("jax_compilation_cache_dir", before)

    def test_env_dir_wins_and_nothing_else_is_set(self, cache_config,
                                                  monkeypatch, tmp_path):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        jax.config.update("jax_compilation_cache_dir", None)
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir is None

    def test_fixed_repo_dir_without_env(self, cache_config, monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        path = enable_compile_cache()
        assert path == str(REPO_ROOT / ".jax_cache") == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path

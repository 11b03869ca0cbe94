"""What the entry points do before and around the simulation: where the
compilation cache goes, how `chip_smoke.py` refuses a machine without a
TPU and compares results, and that a profile that cannot start fails.
"""
import os
import sys

import jax
import pytest

from repro import compile_cache
from repro.telemetry import profiling

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
import chip_smoke  # noqa: E402

CACHE_FLAGS = ("jax_compilation_cache_dir",
               "jax_persistent_cache_min_compile_time_secs")


@pytest.fixture
def cache_flags():
    """Restore JAX's cache flags after a test that sets them."""
    from jax.experimental.compilation_cache import compilation_cache
    saved = {k: getattr(jax.config, k) for k in CACHE_FLAGS}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


class TestCompileCache:
    def test_default_is_git_ignored_dir_in_checkout(self, cache_flags,
                                                    monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        path = compile_cache.enable()
        assert path == os.path.join(ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        with open(os.path.join(ROOT, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()

    def test_env_var_is_left_to_jax(self, cache_flags, monkeypatch,
                                    tmp_path):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        before = jax.config.jax_compilation_cache_dir
        assert compile_cache.enable() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before

    def test_short_compiles_are_cached(self, cache_flags):
        compile_cache.enable()
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0


class TestChipSmoke:
    def test_refuses_a_machine_without_tpu(self, capsys):
        assert jax.devices()[0].platform != "tpu"
        with pytest.raises(SystemExit, match="platform 'cpu'"):
            chip_smoke.main([])
        assert '"ok"' not in capsys.readouterr().out

    def test_integer_counters_must_match_exactly(self):
        d = chip_smoke.Diff()
        d.cell("c", {"slc_writes": 8193.0, "wa_paper": 1.0},
               {"slc_writes": 8192.0, "wa_paper": 1.0})
        assert len(d.bad) == 1 and "slc_writes" in d.bad[0]

    @pytest.mark.parametrize("scale,ok", [(0.5, True), (2.0, False)])
    def test_floats_within_tolerance(self, scale, ok):
        rel = scale * chip_smoke.FLOAT_RTOL
        d = chip_smoke.Diff()
        d.cell("c", {"mean_write_latency_ms": 2.0 * (1 + rel)},
               {"mean_write_latency_ms": 2.0})
        assert (not d.bad) == ok
        assert d.max_rel["mean_write_latency_ms"] == pytest.approx(rel)

    def test_missing_metric_is_a_mismatch(self):
        d = chip_smoke.Diff()
        d.cell("c", {"erases": 0.0}, {"erases": 0.0, "migrations": 0.0})
        assert d.bad and "migrations" in d.bad[0]


def test_profile_that_cannot_start_fails(monkeypatch, tmp_path):
    def refuse(*_, **__):
        raise RuntimeError("no profiler backend")
    monkeypatch.setattr(jax.profiler, "start_trace", refuse)
    with pytest.raises(RuntimeError, match="no profiler backend"):
        with profiling.profile(str(tmp_path)):
            pass


def test_no_profile_requested_is_a_no_op():
    with profiling.profile(None) as running:
        assert running is False

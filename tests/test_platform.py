"""Platform decisions made in one place: kernel interpret mode, device
peaks, and where JAX's compile cache goes."""
import jax
import pytest

from repro.kernels.platform import interpret_mode
from repro.launch import compile_cache
from repro.launch.roofline import PEAKS, peaks


@pytest.mark.parametrize("platform,want", [("cpu", True), ("tpu", False)])
def test_interpret_mode_follows_platform(platform, want):
    assert interpret_mode(None, platform) is want


def test_interpret_mode_explicit_flag_wins_and_unknown_platform_raises():
    assert interpret_mode(False, "cpu") is False
    assert interpret_mode(True, "tpu") is True
    assert interpret_mode() is (jax.default_backend() == "cpu")
    with pytest.raises(RuntimeError, match="gpu"):
        interpret_mode(None, "gpu")


def test_peaks_are_looked_up_never_assumed():
    v5e = peaks("TPU v5 lite")
    assert (v5e.flops_bf16, v5e.hbm_bw, v5e.ici_bw) == (197e12, 819e9, 200e9)
    assert "TPU v5e" in v5e.source
    with pytest.raises(KeyError, match="cpu"):
        peaks("cpu")
    assert set(PEAKS) == {"TPU v5 lite"}


def test_compile_cache_respects_env_and_else_uses_checkout(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert compile_cache.use_compile_cache() == "/elsewhere/cache"
    assert calls == []                         # nothing set in code
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    path = compile_cache.use_compile_cache()
    assert path.endswith(".jax_cache")
    assert (compile_cache.CHECKOUT_CACHE.parent / "pyproject.toml").exists()
    assert calls == [("jax_compilation_cache_dir", path)]

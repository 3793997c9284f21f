"""Process set-up shared by the entry points (``launch.runtime``) and the
roofline's per-chip peaks (``launch.roofline``)."""

import jax
import pytest

from repro.launch import roofline, runtime


@pytest.fixture
def restore_cache_dir():
    prev = jax.config.values["jax_compilation_cache_dir"]
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_cache_env_var_leaves_config_untouched(monkeypatch, tmp_path,
                                               restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.values["jax_compilation_cache_dir"]
    assert runtime.enable_compile_cache() == str(tmp_path)
    assert jax.config.values["jax_compilation_cache_dir"] == before


def test_cache_default_is_one_fixed_repo_dir(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = runtime.enable_compile_cache()
    second = runtime.enable_compile_cache()
    assert first == second == str(runtime.REPO_ROOT / ".jax_cache")
    assert (runtime.REPO_ROOT / "chip_smoke.py").is_file()
    assert jax.config.values["jax_compilation_cache_dir"] == first


def test_child_env_pins_cpu(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    env = runtime.cpu_child_env(XLA_FLAGS="--x")
    assert env["JAX_PLATFORMS"] == "cpu" and env["XLA_FLAGS"] == "--x"


def test_refuse_if_chip_held_passes_off_tpu():
    assert jax.default_backend() != "tpu"
    runtime.refuse_if_chip_held("test")


def test_peaks_keyed_by_device_kind():
    v5e = roofline.peaks_for("TPU v5 lite")
    assert (v5e.flops, v5e.hbm_bw, v5e.ici_bw) == (197e12, 819e9, 200e9)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="TPU v99"):
        roofline.peaks_for("TPU v99")
    with pytest.raises(KeyError, match="TPU v99"):
        roofline.analyze_cell({"hlo": {}, "chips": 1}, device_kind="TPU v99")

"""What the TPU smoke run relies on, checked on the CPU: ``chip_smoke.main``
refuses a backend without a TPU before running anything, and the persistent
compilation cache can be placed from outside (``JAX_COMPILATION_CACHE_DIR``)
or otherwise sits at one fixed directory inside the checkout."""

import importlib.util
import os
import pathlib

import jax
import pytest

from repro.core import episode

ROOT = pathlib.Path(__file__).resolve().parents[1]

_CACHE_OPTIONS = ("jax_compilation_cache_dir",
                  "jax_persistent_cache_min_entry_size_bytes",
                  "jax_persistent_cache_min_compile_time_secs")


@pytest.fixture
def restore_cache_config():
    """Put JAX's persistent-cache options back as they were."""
    before = {name: getattr(jax.config, name) for name in _CACHE_OPTIONS}
    yield
    for name, value in before.items():
        jax.config.update(name, value)


def _load_chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_refuses_a_cpu_backend(capsys):
    assert jax.devices()[0].platform != "tpu"
    smoke = _load_chip_smoke()
    assert smoke.main([]) != 0
    assert smoke.main(["--chips", "4"]) != 0
    out, err = capsys.readouterr()
    assert "no TPU" in err
    assert '"ok"' not in out  # no result line, no phase output
    assert "phase" not in out


def test_compile_cache_respects_jax_compilation_cache_dir(
        tmp_path, monkeypatch, restore_cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert episode.enable_persistent_compilation_cache() == str(tmp_path)
    # JAX reads the variable itself: the directory option is left alone
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_one_in_checkout_dir(
        monkeypatch, restore_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = episode.enable_persistent_compilation_cache()
    second = episode.enable_persistent_compilation_cache()
    assert first == second == str(ROOT / ".jax_cache")
    assert os.path.isdir(first)
    assert jax.config.jax_compilation_cache_dir == first

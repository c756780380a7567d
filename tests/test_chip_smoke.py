"""chip_smoke.py's refusals, and the compile-cache placement it shares with
the benchmarks.

The script measures nothing off the chip: on any other backend, or with the
Pallas kernels forced into interpret mode, it must exit non-zero naming the
cause and print no result line.
"""
import importlib.util
import os
import pathlib

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _load(name, rel):
    spec = importlib.util.spec_from_file_location(name, ROOT / rel)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def cache_config():
    """Restore the process's compile-cache setting after the test."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("interpret, cause", [(False, "no TPU"),
                                              (True, "REPRO_PALLAS_INTERPRET")])
def test_chip_smoke_refuses_without_tpu(monkeypatch, capsys, interpret,
                                        cause):
    if interpret:
        monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    else:
        monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    smoke = _load("chip_smoke", "chip_smoke.py")
    with pytest.raises(SystemExit) as exc:
        smoke.main([])
    assert exc.value.code != 0
    out = capsys.readouterr()
    assert out.out == ""
    assert cause in out.err


@pytest.mark.parametrize("env_set", [False, True])
def test_compile_cache_placement(cache_config, monkeypatch, tmp_path,
                                 env_set):
    """JAX_COMPILATION_CACHE_DIR set: nothing is set in code (JAX reads the
    variable itself).  Unset: the fixed, gitignored <repo>/.jax_cache."""
    if env_set:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    common = _load("bench_common", "benchmarks/common.py")
    jax.config.update("jax_compilation_cache_dir", None)
    common.enable_compile_cache()
    want = None if env_set else os.path.join(str(ROOT), ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == want
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().splitlines()

"""launch/serve.py's entry-point rules: where the compile cache goes, the
memory budget comes from the device, and a run that the wall limit cuts
short fails."""
import importlib.util

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.core.engine import ServingEngine
from repro.launch import serve


@pytest.fixture
def cache_dir_restored():
    """Leave the process's compile cache as the test found it: later
    tests in the same worker must not write into the checkout's cache."""
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    compilation_cache.reset_cache()


def test_compile_cache_fixed_in_checkout(monkeypatch, cache_dir_restored):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    serve.use_compile_cache()
    assert jax.config.jax_compilation_cache_dir == str(
        serve.REPO_ROOT / ".jax_cache")
    assert (serve.REPO_ROOT / "chip_smoke.py").is_file()


def test_compile_cache_env_wins(monkeypatch, cache_dir_restored):
    jax.config.update("jax_compilation_cache_dir", "/set/by/jax/from/env")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/set/by/jax/from/env")
    serve.use_compile_cache()
    assert jax.config.jax_compilation_cache_dir == "/set/by/jax/from/env"


def test_budget_from_device_or_refused():
    """A CPU reports no bytes_limit: only --smoke may plan against the
    stated smoke budget; a full run refuses to guess."""
    assert jax.devices()[0].platform == "cpu"
    assert serve.device_hbm_bytes(smoke=True) == serve.SMOKE_HBM_BYTES
    with pytest.raises(SystemExit, match="bytes_limit"):
        serve.device_hbm_bytes(smoke=False)


def test_one_chip_phase_compiles_one_decode(monkeypatch, cache_dir_restored,
                                            capsys):
    """chip_smoke.py's one-chip phase at smoke widths, on one device and
    no mesh: every request served in full, one decode executable (the
    block-table upload must not give the step a second signature), and
    agreement with the cache-free forward."""
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", serve.REPO_ROOT / "chip_smoke.py")
    chip_smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(chip_smoke)
    monkeypatch.setitem(chip_smoke.PHASES, 1,
                        chip_smoke.PHASES[1] + ["--smoke"])
    chip_smoke.run(1)
    out = capsys.readouterr().out
    assert "+ 1 decode" in out and "0 mismatches" in out


def test_wall_cut_run_exits_nonzero(monkeypatch, cache_dir_restored):
    run = ServingEngine.run
    monkeypatch.setattr(ServingEngine, "run",
                        lambda self, max_wall_s: run(self, max_wall_s=0.0))
    with pytest.raises(SystemExit, match="wall limit cut the run"):
        serve.main(["--smoke", "--requests", "3", "--dataset", "mixed"])

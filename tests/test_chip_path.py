"""The chip path's guards, checked on the CPU: the compile-cache placement,
device naming with no silent fallback, the autotune CLI's exit code, and
``chip_smoke.py`` — refused at its device phase here, and its later
phases rehearsed end to end at a tiny batch."""
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import backend
from repro.runtime.inject import FaultPlan, FaultSpec, inject

ROOT = os.path.join(os.path.dirname(__file__), "..")


@pytest.fixture
def jax_cache_config():
    """Restore JAX's compile-cache settings after a test moves them."""
    from jax.experimental.compilation_cache import compilation_cache
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def test_compile_cache_goes_where_the_variable_says(tmp_path, monkeypatch,
                                                     jax_cache_config):
    from jax.experimental.compilation_cache import compilation_cache
    cache = tmp_path / "cc"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(cache))
    default_before = set(os.listdir(backend.DEFAULT_COMPILE_CACHE)) \
        if os.path.isdir(backend.DEFAULT_COMPILE_CACHE) else set()
    assert backend.configure_compile_cache() == str(cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compilation_cache.reset_cache()
    jax.jit(lambda x: x * 7.0 - 3.0)(jnp.ones(5)).block_until_ready()
    assert any(n.endswith("-cache") for n in os.listdir(cache))
    default_after = set(os.listdir(backend.DEFAULT_COMPILE_CACHE)) \
        if os.path.isdir(backend.DEFAULT_COMPILE_CACHE) else set()
    assert default_after == default_before


def test_compile_cache_defaults_to_fixed_checkout_path(monkeypatch,
                                                       jax_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = backend.configure_compile_cache()
    assert path == os.path.join(os.path.realpath(ROOT), ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_device_is_named_and_backend_failure_is_not_hidden(monkeypatch):
    info = backend.device_info()
    assert info == {"platform": "cpu", "kind": jax.devices()[0].device_kind,
                    "count": len(jax.devices())}

    def broken():
        raise RuntimeError("backend failed to initialize")
    monkeypatch.setattr(jax, "default_backend", broken)
    with pytest.raises(RuntimeError, match="failed to initialize"):
        backend.on_tpu()


def test_autotune_cli_fails_on_any_skipped_candidate(tmp_path, monkeypatch,
                                                     capsys):
    from repro.service import __main__ as cli
    monkeypatch.setattr(cli, "configure_compile_cache", lambda: None)
    argv = ["autotune", "--net", "mlp", "--batch", "2", "-k", "2",
            "--iters", "1", "--store-dir", str(tmp_path)]
    assert cli.main(argv) == 0
    report = json.loads(capsys.readouterr().out)
    assert report["device"]["platform"] == "cpu"
    assert report["n_executed"] == report["n_candidates"] >= 1
    plan = FaultPlan.make(3, {"autotune.measure": FaultSpec(
        rate=1.0, kind="error", match="0")})
    with inject(plan):
        assert cli.main(argv) == 1
    err = capsys.readouterr().err
    assert "candidate 0 skipped" in err and "InjectedFault" in err


def _chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_refuses_the_cpu_at_the_device_phase(capsys):
    assert _chip_smoke().main() == 1
    out, err = capsys.readouterr()
    assert "platform 'cpu'" in err
    assert '"ok"' not in out


def test_chip_smoke_phases_rehearse_on_cpu(tmp_path, capsys):
    """Phases 2-4 of the chip run, at batch 1: cold then cached, every
    layer within the oracle tolerance, every candidate executed."""
    res = _chip_smoke().run_phases("resnet", 1, str(tmp_path), k=2)
    assert res["serve"]["sources"] == ["cold", "cached", "cached"]
    assert res["execute"]["layers"] == 72
    assert res["execute"]["max_rel_err"] < res["execute"]["tolerance"]
    assert res["autotune"]["n_executed"] == 2
    assert res["autotune"]["device"]["platform"] == "cpu"
    assert "execute:" in capsys.readouterr().out

"""The chip smoke script and the compile-cache placement, off the chip:
the script must refuse a CPU, its phases must agree on a small CPU queue,
and the cache directory must come from the environment or sit at a fixed,
git-ignored path inside the checkout."""

import sys
from dataclasses import replace
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402

from repro import compile_cache  # noqa: E402


def test_chip_smoke_refuses_cpu(capsys):
    assert jax.devices()[0].platform != "tpu"
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert exc.value.code not in (0, None)
    out = capsys.readouterr().out
    assert '"ok"' not in out
    assert "platform=cpu" in out


def test_chip_smoke_phases_agree_on_a_small_queue():
    """Served drain (cold == warm) and standalone reference agree on a
    reduced federation: two preference trials, int8 and async."""
    specs = chip_smoke.served_specs()
    specs = [replace(s, m0=4, reduced=True)
             for s in specs[:2] + specs[15:17]]
    served, walls = chip_smoke.serve_phase(specs)
    assert sorted(served) == sorted(s.key() for s in specs)
    assert len(walls) == 2
    assert chip_smoke.reference_phase(specs, served) == []


@pytest.fixture
def cache_config():
    """Restore the cache settings ``enable_compile_cache`` changes."""
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs")
    before = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in before.items():
        jax.config.update(n, v)


def test_compile_cache_honours_environment(monkeypatch, cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0


def test_compile_cache_defaults_into_the_checkout(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    ignored = (ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored

"""chip_smoke.py's phases at a tiny size on the 8-device CPU mesh.

The phase functions are called directly with small sizes and the CPU
platform; the script itself only ever runs them at full size on a TPU.
"""
import os
import shutil
import subprocess
import sys

import pytest

import chip_smoke as S

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(phases):
    lines = []
    out = S.run(phases, log=lines.append)
    assert list(out) == [name for name, _ in phases]
    for name, (checks, *_rest) in out.items():
        assert checks > 0, name
    return out, lines


def test_one_chip_phases_on_cpu_mesh(mpi, world):
    phases = S.one_chip_phases(mpi, platform="cpu", count=world.size,
                               small=2, big=64, alg=64, host=256,
                               steps=2, batch=4, flash=(2, 128, 128),
                               interpret=True)
    out, lines = _run(phases)
    assert list(out) == ["device", "default_selection", "coll_xla",
                         "host_staging", "train_step", "flash_kernel"]
    assert any(ln.startswith("coll/xla comm:") for ln in lines)
    assert any(ln.startswith("native: built") for ln in lines), lines


def test_four_chip_phases_on_cpu_mesh(mpi, world):
    phases = S.four_chip_phases(mpi, platform="cpu", count=world.size,
                                big=64, alg=64, flagship=4)
    out, lines = _run(phases)
    assert list(out) == ["device", "collectives", "algorithms", "split",
                         "flagship"]
    # every lowering and every root compiled its own schedule
    assert out["algorithms"][0] >= (
        len(S.XLA_ALLREDUCE_ALGORITHMS) * (world.size + 1)
        + len(S.ROOT_ALGORITHMS) * world.size * 2)


def test_a_failing_phase_fails_the_run():
    def bad(chk, log):
        chk.equal([1.0], [2.0], "bad")

    def never_reached(chk, log):
        raise AssertionError("ran after a failure")
    with pytest.raises(S.SmokeError, match="bad: mismatch"):
        S.run([("bad", bad), ("after", never_reached)], log=lambda s: None)


def test_a_phase_that_checks_nothing_fails_the_run():
    with pytest.raises(S.SmokeError, match="checked nothing"):
        S.run([("empty", lambda chk, log: None)], log=lambda s: None)


def test_main_fails_without_a_chip(mpi, capsys, monkeypatch, tmp_path):
    # a cache dir of its own: main must not configure this process's
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert S.main([]) == 1
    out = capsys.readouterr().out
    assert '"ok"' not in out
    assert "platform=cpu" in out


def test_script_alone_fails(tmp_path):
    shutil.copy(os.path.join(_REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    res = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         env=env, capture_output=True, text=True,
                         timeout=120)
    assert res.returncode != 0
    assert '"ok"' not in res.stdout
    assert "ompi_tpu" in res.stderr


def test_compile_cache_dir(monkeypatch):
    """$JAX_COMPILATION_CACHE_DIR or a directory the program configured
    wins untouched; without either the cache is the checkout's fixed,
    gitignored .jax_cache/."""
    import jax
    from ompi_tpu.runtime.init import compile_cache_dir
    before = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert compile_cache_dir() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        jax.config.update("jax_compilation_cache_dir", "/mine")
        assert compile_cache_dir() == "/mine"
        assert jax.config.jax_compilation_cache_dir == "/mine"
        jax.config.update("jax_compilation_cache_dir", None)
        repo_cache = os.path.join(_REPO, ".jax_cache")
        assert compile_cache_dir() == repo_cache
        assert jax.config.jax_compilation_cache_dir == repo_cache
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    with open(os.path.join(_REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()

"""Where the entry points keep JAX's persistent compilation cache
(``repro.launch.compile_cache``): the environment's directory when
``JAX_COMPILATION_CACHE_DIR`` is set, else the checkout's fixed
``.jax_cache/``; importing ``repro`` places none."""

import os
import pathlib
import subprocess
import sys

import jax

from repro.launch import compile_cache

ROOT = pathlib.Path(__file__).resolve().parents[1]


def test_env_directory_wins_and_nothing_is_set(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    was = jax.config.jax_compilation_cache_dir
    assert compile_cache.place_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == was


def test_default_is_the_checkout_jax_cache(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        got = compile_cache.place_compile_cache()
        assert got == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
    ignored = (ROOT / ".gitignore").read_text().splitlines()
    assert ".jax_cache/" in ignored


def test_importing_repro_places_no_cache():
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(PYTHONPATH=str(ROOT / "src"), JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "-c",
         "import jax, repro, repro.launch.serve, repro.launch.train; "
         "print(jax.config.jax_compilation_cache_dir)"],
        capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.strip().splitlines()[-1] == "None"

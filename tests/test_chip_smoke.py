"""Where the compile cache lands, and chip_smoke.py's refusal off the chip."""
import os
import pathlib
import subprocess
import sys
import textwrap

import jax

from repro.launch import compile_cache

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _run(argv, env_update, timeout=120):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_update)
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, *argv], capture_output=True, text=True, env=env,
        cwd=ROOT, timeout=timeout,
    )


def test_cache_env_var_is_honoured_and_untouched(tmp_path):
    code = """
        import os, jax, jax.numpy as jnp
        from repro.launch.compile_cache import use_compilation_cache

        assert use_compilation_cache() == os.environ["JAX_COMPILATION_CACHE_DIR"]
        assert jax.config.jax_compilation_cache_dir == os.environ["JAX_COMPILATION_CACHE_DIR"]
        jax.block_until_ready(jax.jit(lambda x: x * 2.0 + 1.0)(jnp.ones((8,))))
        print("OK")
    """
    r = _run(["-c", textwrap.dedent(code)], {
        "PYTHONPATH": str(ROOT / "src"),
        "JAX_COMPILATION_CACHE_DIR": str(tmp_path),
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
    })
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OK" in r.stdout
    assert any(tmp_path.iterdir())  # the compiled program was cached there


def test_cache_defaults_to_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.use_compilation_cache()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert path == str(ROOT / ".jax_compilation_cache")
    ignored = (ROOT / ".gitignore").read_text().splitlines()
    assert ".jax_compilation_cache/" in ignored


def test_chip_smoke_refuses_the_cpu():
    r = _run(["chip_smoke.py"], {})
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "needs a TPU backend" in r.stderr

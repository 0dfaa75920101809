"""Where the entry points put JAX's persistent compilation cache. Each case
runs in its own interpreter, so the cache never turns on in the test
process."""

import os
import subprocess
import sys
import textwrap

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(body: str, env: dict) -> str:
    script = textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {os.path.join(ROOT, 'src')!r})
        import jax, jax.numpy as jnp
        from repro.launch.compile_cache import DEFAULT_DIR, enable_compile_cache
    """) + textwrap.dedent(body)
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=300, env=env)
    assert r.returncode == 0, r.stderr[-3000:]
    return r.stdout


def test_environment_directory_is_used_and_nothing_else(tmp_path):
    cache = tmp_path / "cache"
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(cache)}
    out = _run("""
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        print(enable_compile_cache())
        print(jax.config.jax_compilation_cache_dir)
        jax.jit(lambda x: jnp.sin(x) * 2)(jnp.ones(8)).block_until_ready()
    """, env)
    assert out.split() == [str(cache), str(cache)]
    assert any(cache.iterdir()), "nothing was cached in the given directory"


def test_fixed_repo_directory_without_environment():
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    out = _run("""
        print(enable_compile_cache())
        print(jax.config.jax_compilation_cache_dir)
        print(DEFAULT_DIR)
    """, env)
    want = os.path.join(ROOT, ".jax_cache")
    assert out.split() == [want, want, want]

"""``repro.compile_cache.enable``: where the entry points keep JAX's
persistent compilation cache. Each case runs in a fresh interpreter, since
the cache directory is process-wide JAX state."""
import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = textwrap.dedent("""
    import sys
    import jax, jax.numpy as jnp
    from repro import compile_cache
    # JAX keeps only compiles over a second; keep this tiny one too
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    path = compile_cache.enable()
    if sys.argv[1:] == ["compile"]:
        jax.jit(lambda x: x * 2 + 1)(jnp.arange(8.0)).block_until_ready()
    print(path)
    print(jax.config.jax_compilation_cache_dir)
""")


def _run(env_dir, *args):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run([sys.executable, "-c", SCRIPT, *args], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    return out.stdout.split()[-2:]


def test_env_dir_is_used_and_written(tmp_path):
    d = str(tmp_path / "cache")
    path, configured = _run(d, "compile")
    assert path == configured == d
    assert os.listdir(d), "nothing was written to JAX_COMPILATION_CACHE_DIR"


def test_default_dir_is_repo_local():
    from repro import compile_cache
    path, configured = _run(None)
    assert path == configured == compile_cache.DEFAULT_DIR
    assert path == os.path.join(REPO, ".jax_cache")

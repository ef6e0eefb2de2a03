"""The chip entry point and the compile-cache placement, checked on the CPU
in child processes pinned to ``JAX_PLATFORMS=cpu`` (a child that probed for
the accelerator would compete with whichever process holds it)."""
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _cpu_env(**extra):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=os.path.join(ROOT, "src"),
               **extra)
    return env


def test_chip_smoke_refuses_to_run_without_a_tpu():
    res = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                         capture_output=True, text=True, timeout=300,
                         env=_cpu_env(), cwd=ROOT)
    assert res.returncode != 0
    assert "no TPU" in res.stderr and "'cpu'" in res.stderr, res.stderr
    assert '"ok"' not in res.stdout


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_placement(tmp_path, from_env):
    """``JAX_COMPILATION_CACHE_DIR`` wins and receives the entries;
    without it the cache is ``<checkout>/.jax_cache``."""
    from repro.compile_cache import DEFAULT_DIR
    assert DEFAULT_DIR == os.path.join(ROOT, ".jax_cache")
    cache = tmp_path / "cache"
    code = textwrap.dedent(f"""
        import os
        import jax, jax.numpy as jnp
        from repro.compile_cache import DEFAULT_DIR, configure_compile_cache
        want = os.environ.get("JAX_COMPILATION_CACHE_DIR", DEFAULT_DIR)
        assert configure_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        if {from_env}:
            jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
            jax.jit(lambda x: x * 3.0 + 1.0)(jnp.arange(7.0)).block_until_ready()
        print("CACHE_OK")
    """)
    extra = {"JAX_COMPILATION_CACHE_DIR": str(cache)} if from_env else {}
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300, env=_cpu_env(**extra),
                         cwd=str(tmp_path))
    assert "CACHE_OK" in res.stdout, res.stderr[-3000:]
    if from_env:
        written = set(os.listdir(cache))
        assert written
        if os.path.isdir(DEFAULT_DIR):
            assert not written & set(os.listdir(DEFAULT_DIR))


def test_compile_cache_keeps_each_programs_scope_names(tmp_path):
    """Two programs that differ only in a ``jax.named_scope`` get their own
    cache entries: the second does not load the first's executable, whose
    metadata names the first's scope."""
    code = textwrap.dedent("""
        import sys
        import jax, jax.numpy as jnp
        from repro.compile_cache import configure_compile_cache
        configure_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

        def f(x):
            with jax.named_scope(sys.argv[1]):
                return jnp.sin(x) * 3.0 + 1.0
        text = jax.jit(f).lower(jnp.arange(7.0)).compile().as_text()
        print(sorted(n for n in ("scope_first", "scope_second")
                     if n in text))
    """)
    env = _cpu_env(JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    for name in ("scope_first", "scope_second"):
        res = subprocess.run([sys.executable, "-c", code, name],
                             capture_output=True, text=True, timeout=300,
                             env=env, cwd=ROOT)
        assert res.returncode == 0, res.stderr
        assert res.stdout.strip().splitlines()[-1] == repr([name])

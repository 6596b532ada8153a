"""Process set-up helpers shared by the entry points (launch/runtime.py)."""
import os
import subprocess
import sys

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_compile_cache_lands_only_in_the_env_dir(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, entries are written there and
    the helper configures no other directory.  Runs in a child process so
    this test process never turns the persistent cache on."""
    cache = tmp_path / "jcc"
    script = (
        "import jax, jax.numpy as jnp\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
        "from repro.launch.runtime import enable_compile_cache\n"
        "print(enable_compile_cache())\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
        "jax.jit(lambda x: jnp.sin(x) * 3)(jnp.ones(4)).block_until_ready()\n"
    )
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache))
    r = subprocess.run([sys.executable, "-c", script], capture_output=True, text=True,
                       env=env, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.split() == [str(cache), str(cache)]
    assert any(cache.iterdir()), "no cache entry was written"


def test_default_cache_dir_is_inside_the_checkout():
    from repro.launch.runtime import DEFAULT_CACHE_DIR, REPO_ROOT

    assert (REPO_ROOT / "src" / "repro" / "launch" / "runtime.py").is_file()
    assert DEFAULT_CACHE_DIR == REPO_ROOT / ".jax_cache"


def test_device_label_names_platform_kind_and_count():
    import jax

    from repro.launch.runtime import device_info, device_label

    info = device_info()
    assert info == {"platform": jax.devices()[0].platform,
                    "kind": jax.devices()[0].device_kind, "count": len(jax.devices())}
    assert device_label() == (f"platform={info['platform']} kind={info['kind']} "
                              f"count={info['count']}")

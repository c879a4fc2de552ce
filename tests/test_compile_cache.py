"""Where ``repro.launch.compile_cache`` puts JAX's persistent cache.

Each case runs in a fresh interpreter: the cache directory is process
state that JAX fixes at its first compile.
"""
import json

CHILD = """
import json, os
{env}
import jax, jax.numpy as jnp
from repro.launch.compile_cache import DEFAULT_DIR, enable_compile_cache
before = sorted(os.listdir(DEFAULT_DIR)) if DEFAULT_DIR.is_dir() else None
where = enable_compile_cache()
{compile}
after = sorted(os.listdir(DEFAULT_DIR)) if DEFAULT_DIR.is_dir() else None
print(json.dumps({{
    "where": where,
    "config": jax.config.jax_compilation_cache_dir,
    "default": str(DEFAULT_DIR),
    "default_untouched": before == after,
}}))
"""


def _run(subproc, env="", compile=""):
    out = subproc(CHILD.format(env=env, compile=compile))
    return json.loads(out.strip().splitlines()[-1])


def test_cache_dir_from_environment(subproc, tmp_path):
    cache = tmp_path / "cc"
    rec = _run(
        subproc,
        env=(
            f"os.environ['JAX_COMPILATION_CACHE_DIR'] = {str(cache)!r}\n"
            "os.environ['JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS'] = '0'"
        ),
        compile="jax.jit(lambda a: jnp.sin(a) * 3)(jnp.arange(8.0)).block_until_ready()",
    )
    assert rec["where"] == rec["config"] == str(cache)
    assert cache.is_dir() and any(cache.iterdir())  # the program went there
    assert rec["default_untouched"]  # and not to the repo's .jax_cache/


def test_cache_dir_defaults_to_repo(subproc):
    rec = _run(subproc, env="os.environ.pop('JAX_COMPILATION_CACHE_DIR', None)")
    assert rec["where"] == rec["config"] == rec["default"]
    assert rec["default"].endswith("/.jax_cache")

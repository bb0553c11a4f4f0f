"""Where compiled programs are cached across processes.

JAX_COMPILATION_CACHE_DIR, when set, is the only place entries land;
unset, they land in the repository's fixed `.jax_cache/`
(`repro.utils.compile_cache`). Each case compiles a uniquely named
program in a fresh process (the cache is looked up once per process)
and looks for its entry.
"""
import os
import subprocess
import sys
import uuid

from repro.utils.compile_cache import ENV_VAR, REPO_CACHE

SRC = os.path.join(os.path.dirname(__file__), os.pardir, "src")

SCRIPT = r"""
import sys
import jax, jax.numpy as jnp
from repro.utils.compile_cache import enable_compile_cache
where = enable_compile_cache()
def probe(x):
    return jnp.sin(x) * 3.25
probe.__name__ = sys.argv[1]
jax.jit(probe)(jnp.ones(8)).block_until_ready()
print(where)
"""


def _compile_probe(cache_dir):
    token = f"cache_probe_{uuid.uuid4().hex}"
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    env.pop(ENV_VAR, None)
    if cache_dir is not None:
        env[ENV_VAR] = str(cache_dir)
    out = subprocess.run([sys.executable, "-c", SCRIPT, token], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    return token, out.stdout.strip().splitlines()[-1]


def _entries(directory, token):
    if not os.path.isdir(directory):
        return []
    return [f for f in os.listdir(directory) if token in f]


def test_compile_cache_follows_the_environment_variable(tmp_path):
    token, where = _compile_probe(tmp_path)
    assert where == str(tmp_path)
    assert _entries(tmp_path, token)
    assert not _entries(REPO_CACHE, token)


def test_compile_cache_defaults_to_the_repository_directory():
    token, where = _compile_probe(None)
    assert where == REPO_CACHE
    entries = _entries(REPO_CACHE, token)
    try:
        assert entries
    finally:
        for f in entries:
            os.remove(os.path.join(REPO_CACHE, f))

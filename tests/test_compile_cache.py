"""Where the entry points keep JAX's persistent compilation cache.

Each case compiles in a fresh interpreter (the cache directory is read once
per process), with the minimum compile time for caching set to zero so a
tiny program is written.
"""
import os
import pathlib
import subprocess
import sys

from _subproc import REPO, SRC

_SNIPPET = """
import jax, jax.numpy as jnp
from repro.compile_cache import enable_compile_cache
print(enable_compile_cache())
def {name}(x):
    return jnp.sin(x) * 1.25
jax.block_until_ready(jax.jit({name})(jnp.ones(7)))
"""


def _compile_in_child(name: str, cache_env):
    env = dict(os.environ)
    env['PYTHONPATH'] = SRC + os.pathsep + env.get('PYTHONPATH', '')
    env['JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS'] = '0'
    env.pop('JAX_COMPILATION_CACHE_DIR', None)
    if cache_env is not None:
        env['JAX_COMPILATION_CACHE_DIR'] = str(cache_env)
    proc = subprocess.run([sys.executable, '-c', _SNIPPET.format(name=name)],
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 0, proc.stderr
    return pathlib.Path(proc.stdout.splitlines()[-1])


def _entries(d: pathlib.Path, name: str) -> list:
    """Cache entries JAX wrote for the jitted function ``name``."""
    if not d.is_dir():
        return []
    return [e for e in os.listdir(d) if e.startswith(f'jit_{name}-')]


def test_cache_honours_env_var(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, entries appear there and nowhere
    in the checkout."""
    used = _compile_in_child('probe_env_cache', tmp_path)
    assert used == tmp_path
    assert _entries(tmp_path, 'probe_env_cache')
    assert not _entries(REPO / '.jax_cache', 'probe_env_cache')


def test_cache_defaults_to_checkout():
    """Unset, the cache is the fixed ``<checkout>/.jax_cache``, which git
    ignores."""
    used = _compile_in_child('probe_default_cache', None)
    assert used == REPO / '.jax_cache'
    assert _entries(used, 'probe_default_cache')
    assert '.jax_cache/' in (REPO / '.gitignore').read_text().split()

"""Where JAX's persistent compilation cache goes
(mxnet_tpu/compile_cache/jax_cache.py): placed from outside when
JAX_COMPILATION_CACHE_DIR is set, else at one fixed path inside the
checkout, and a second process that compiles the same program hits it."""
import json
import os
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHILD = r"""
import json, sys
import jax
before = jax.config.jax_compilation_cache_dir
from mxnet_tpu.compile_cache import jax_cache
cache = jax_cache.configure()
out = {"before": before, "after": jax.config.jax_compilation_cache_dir,
       "directory": cache.directory}
if "--compile" in sys.argv:
    import jax.numpy as jnp
    jax.jit(lambda x: jnp.tanh(x @ x).sum())(jnp.ones((64, 64))
                                             ).block_until_ready()
    out["counts"] = cache.counts()
print(json.dumps(out))
"""


def _child(env_extra, *argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               # JAX's thresholds would keep a toy program out of the cache
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(env_extra)
    p = subprocess.run([sys.executable, "-c", _CHILD, *argv], env=env,
                       cwd=_REPO, capture_output=True, text=True,
                       timeout=240)
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_env_placed_cache_sets_nothing_in_code_and_second_run_hits(tmp_path):
    where = str(tmp_path / "placed")
    first = _child({"JAX_COMPILATION_CACHE_DIR": where}, "--compile")
    # JAX read the variable itself; configure() changed no config
    assert first["before"] == first["after"] == first["directory"] == where
    assert first["counts"]["misses"] >= 1 and first["counts"]["hits"] == 0
    assert os.listdir(where)
    assert not os.path.exists(os.path.join(str(tmp_path), ".jax_cache"))
    second = _child({"JAX_COMPILATION_CACHE_DIR": where}, "--compile")
    assert second["counts"]["hits"] >= 1 and second["counts"]["misses"] == 0


def test_unset_the_cache_is_one_fixed_path_in_the_checkout():
    a, b = _child({}), _child({})
    assert a["before"] is None
    assert a["directory"] == b["directory"] \
        == os.path.join(_REPO, ".jax_cache")

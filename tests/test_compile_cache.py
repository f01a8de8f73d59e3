"""The entry points' persistent compile cache (``repro.launch.compile_cache``).

Each case compiles in a fresh process, since the cache settings are global
to a JAX process, and points the checkout fallback at a temporary
directory so the test writes nothing into the checkout.
"""

import json
import os
import subprocess
import sys

import jax

_SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "src")

_SCRIPT = """
import json, pathlib, sys
import jax, jax.numpy as jnp
events = []
jax.monitoring.register_event_listener(lambda e, **_: events.append(e))
from repro.launch import compile_cache
compile_cache.CHECKOUT_CACHE_DIR = pathlib.Path(sys.argv[1])
used = compile_cache.enable()
jax.jit(lambda x: x * 2.0 + 1.0)(jnp.arange(3.0)).block_until_ready()
print(json.dumps({"dir": used,
                  "hits": events.count("/jax/compilation_cache/cache_hits")}))
"""


def _run(fallback, env_dir=None) -> dict:
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = _SRC + os.pathsep + env.get("PYTHONPATH", "")
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    proc = subprocess.run([sys.executable, "-c", _SCRIPT, str(fallback)],
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_second_run_in_the_checkout_reads_the_cache(tmp_path):
    fallback = tmp_path / "checkout_cache"
    first = _run(fallback)
    assert first["dir"] == str(fallback)
    assert first["hits"] == 0
    assert any(fallback.iterdir())
    second = _run(fallback)
    assert second["hits"] >= 1


def test_env_dir_is_left_to_jax_and_checkout_dir_untouched(tmp_path):
    fallback, env_dir = tmp_path / "checkout_cache", tmp_path / "env_cache"
    out = _run(fallback, env_dir)
    assert out["dir"] == str(env_dir)
    assert not fallback.exists()


def test_importing_the_entry_points_sets_no_cache():
    import repro.launch.serve  # noqa: F401
    import repro.launch.train  # noqa: F401
    assert not jax.config.jax_compilation_cache_dir

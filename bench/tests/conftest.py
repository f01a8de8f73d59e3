import os
import sys

import pytest

# the program lives in src/ of the checkout; the benchmark is the package
# ``bench`` at its root
_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _p in (os.path.join(_ROOT, "src"), _ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)


@pytest.fixture
def fresh():
    """Compiled steps and executors are cached by the program across runs;
    a planted fault must not reach (or leave behind) a cached one."""
    import jax
    from repro.core import runner
    from repro.train import steps

    def clear():
        steps._BUNDLE_CACHE.clear()
        runner.reset_executable_caches()
        jax.clear_caches()

    clear()
    yield
    clear()

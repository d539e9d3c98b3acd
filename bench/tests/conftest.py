"""The benchmark's own tests run on the CPU, at the rehearsal sizes."""

import os
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def pytest_configure(config):
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          tempfile.mkdtemp(prefix="bench-tests-jax-cache-"))
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)

"""The rehearsal runs on the CPU (the node processes inherit the
variables). Its CPU programs go to a compile cache of their own: stored with
the zeroed thresholds beside the chip's programs in ``<checkout>/.jax_cache``
they changed the timing of the repo's own tests enough to fail two of them
(tests/test_memtrack.py, PR 23)."""
import json
import os
import shutil
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault(
    "XLA_FLAGS", "--xla_force_host_platform_device_count=4")
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".work", "jax_cache_cpu"))
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if CHECKOUT not in sys.path:
    sys.path.insert(0, CHECKOUT)

TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "tiny")


@pytest.fixture
def toy(tmp_path):
    """A copy of the toy benchmark whose ``llama-tiny`` configuration a test
    may edit: ``toy(key=value)`` sets a key, ``toy(key=None)`` takes it out;
    the copy's root comes back."""
    root = str(tmp_path / "tiny")
    shutil.copytree(TINY, root)
    path = os.path.join(root, "benchmarks", "configs", "llama-tiny.json")

    def edit(**keys):
        with open(path) as f:
            config = json.load(f)
        for key, value in keys.items():
            config.pop(key) if value is None else config.update({key: value})
        with open(path, "w") as f:
            json.dump(config, f)
        return root

    return edit

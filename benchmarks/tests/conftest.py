"""The rehearsal runs on the CPU (the node processes inherit the
variables). Its CPU programs go to a compile cache of their own: stored with
the zeroed thresholds beside the chip's programs in ``<checkout>/.jax_cache``
they changed the timing of the repo's own tests enough to fail two of them
(tests/test_memtrack.py, PR 23)."""
import os
import sys

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

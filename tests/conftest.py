"""Test fixture: run everything on 8 virtual CPU devices.

TPU translation of the reference's multi-process-without-cluster trick
(`mpirun -np 2 pytest` on localhost CPU, reference .travis.yml:96-103):
``--xla_force_host_platform_device_count=8`` gives one process eight XLA
"replicas" so collective correctness runs anywhere (SURVEY.md §4).

This must happen before the first JAX backend use: tests force the CPU
platform in-process, whatever the environment selects.
"""

import os
import sys

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=8"
).strip()
os.environ["JAX_PLATFORMS"] = "cpu"
# A compile cache placed from outside would switch on the persistent
# cache and the warm-start manifest for every hvd.init() in the suite
# (core/state.compile_cache_dir); the tests that exercise the rule set
# their own directory.
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
# Keras 3 binds its backend at first import.  TF ships keras, so a test
# file importing tensorflow before test_keras_frontend.py would silently
# bind the TF backend and hand the keras frontend symbolic tf.Tensors;
# pin the JAX backend for every ordering.
os.environ.setdefault("KERAS_BACKEND", "jax")
# hvd-analyze lock-order detector on for the whole tier-1 suite (and,
# via env inheritance, every multi-process scenario it launches): any
# lock-acquisition cycle raises LockOrderError in whichever test first
# exhibits the ordering (analysis/lockorder.py).  Must be set before
# horovod_tpu creates its locks.
os.environ.setdefault("HVD_TPU_LOCK_CHECK", "1")
# XLA executable-launch counting on for the whole suite
# (utils/xla_dispatch.py): every megakernel launch is wrapped in a
# thread-local dispatch window, so the "exactly one executable per
# fusion group" contract is continuously accumulated on
# ops.megakernel.stats and asserted by tests/test_megakernel.py —
# eager-op creep inside the fused executor fails the suite, not just
# the dedicated test's scenario.
os.environ.setdefault("HVD_TPU_COUNT_DISPATCHES", "1")
# hvd-race: the lockset data-race detector + thread-role asserts
# (analysis/races.py, analysis/threads.py — the env also gates the
# race_checked descriptors, so it must be set before horovod_tpu
# defines its classes) and the donation-lifetime sanitizer
# (analysis/donation.py) armed suite-wide, like the lock-order
# detector above: a guarded-field access no single lock protects, a
# cross-role method entry, or a stale read of a donated buffer raises
# its named error in whichever test first exhibits it.
os.environ.setdefault("HVD_TPU_RACE_CHECK", "1")
os.environ.setdefault("HVD_TPU_DONATION_CHECK", "1")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import atexit  # noqa: E402
import shutil  # noqa: E402
import tempfile  # noqa: E402

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# One persistent XLA compilation cache for the session.  The suite
# re-initializes the runtime hundreds of times, and every hvd.init()
# builds fresh jitted collective kernels that XLA:CPU would compile
# again from identical HLO — about 200 s of the 870 s tier-1 budget.
# This is jax's own cache only: the package's rule
# (core/state.compile_cache_dir) stays off, see the pop above.
_xla_cache = tempfile.mkdtemp(prefix="hvd_tpu_test_xla_cache_")
atexit.register(shutil.rmtree, _xla_cache, ignore_errors=True)
jax.config.update("jax_compilation_cache_dir", _xla_cache)
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running test (multi-process spawns, "
        "interpret-mode pallas backward passes)")
    # The int64 wire-dtype tests intentionally run without jax_enable_x64
    # (values stay in int32 range); jax's truncation notice is expected.
    config.addinivalue_line(
        "filterwarnings",
        "ignore:Explicitly requested dtype.*int64.*:UserWarning")


@pytest.fixture()
def hvd():
    """Initialized horovod_tpu over all 8 virtual devices; fresh per test."""
    import horovod_tpu as hvd

    hvd.init(devices=jax.devices())
    yield hvd
    hvd.shutdown()


@pytest.fixture()
def hvd2():
    """Initialized over a 2-device subset (matches the reference's
    mpirun -np 2 test topology)."""
    import horovod_tpu as hvd

    hvd.init(devices=jax.devices()[:2])
    yield hvd
    hvd.shutdown()

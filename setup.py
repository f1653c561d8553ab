"""Build system for horovod_tpu (≙ reference setup.py, SURVEY.md §2.2 P9).

The reference probes compilers/MPI/CUDA/NCCL at build time and gates
plugins with HOROVOD_WITH[OUT]_* env vars (reference setup.py:63-384,
:541-577).  The TPU build has exactly one native artifact — the host-side
runtime library ``horovod_tpu/native/libhvdtpu.so`` (coordinator, wire,
timeline, handle manager) — so the probing reduces to:

* C++ flag probing (``-std=c++17``, falling back only on error) via a
  test compile, mirroring the reference's ``test_compile`` approach
  (setup.py:63-87);
* ``HOROVOD_TPU_WITHOUT_NATIVE=1`` skips the native build (pure-Python
  fallbacks keep full behavior);
* ``HOROVOD_TPU_WITH_NATIVE=1`` makes a native build failure fatal
  instead of a warning (≙ the skip-vs-require logic, setup.py:541-577).

The library is an ordinary ``g++ -shared`` product, not a Python
extension: Python binds via ctypes (no pybind11 in the image).
"""

import os
import subprocess
import sys
import tempfile
import textwrap

from setuptools import Command, find_packages, setup
from setuptools.command.build_py import build_py

NATIVE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "horovod_tpu", "native")
NATIVE_SOURCES = ["wire.cc", "coordinator.cc", "handle_manager.cc",
                  "timeline.cc"]
NATIVE_TARGET = "libhvdtpu.so"


def _check_output(cmd, **kw):
    return subprocess.run(cmd, check=True, capture_output=True, **kw)


def probe_cxx_flags(cxx="g++"):
    """Find a working flag set with a test compile (≙ reference
    setup.py:63-87 get_cpp_flags)."""
    base = ["-O3", "-fPIC", "-shared", "-pthread"]
    candidates = [["-std=c++17"], ["-std=c++14"]]
    src = textwrap.dedent("""
        #include <unordered_map>
        #include <mutex>
        int main() { std::unordered_map<int, int> m; m[1] = 2; return 0; }
    """)
    with tempfile.TemporaryDirectory() as td:
        cc = os.path.join(td, "probe.cc")
        with open(cc, "w") as f:
            f.write(src)
        for extra in candidates:
            try:
                _check_output([cxx, *base, *extra, cc, "-o",
                               os.path.join(td, "probe.so")])
                return base + extra
            except Exception:
                continue
    raise RuntimeError(
        "could not find working C++ compile flags; set "
        "HOROVOD_TPU_WITHOUT_NATIVE=1 to skip the native library")


def build_native():
    cxx = os.environ.get("CXX", "g++")
    flags = probe_cxx_flags(cxx)
    out = os.path.join(NATIVE_DIR, NATIVE_TARGET)
    srcs = [os.path.join(NATIVE_DIR, s) for s in NATIVE_SOURCES]
    print(f"building {NATIVE_TARGET}: {cxx} {' '.join(flags)}")
    _check_output([cxx, *flags, *srcs, "-o", out])
    return out


class build_py_with_native(build_py):
    """Compile the native runtime alongside the Python sources."""

    def run(self):
        if os.environ.get("HOROVOD_TPU_WITHOUT_NATIVE"):
            print("HOROVOD_TPU_WITHOUT_NATIVE set - skipping native "
                  "runtime (pure-Python fallbacks will be used)")
        else:
            try:
                build_native()
            except Exception as e:
                if os.environ.get("HOROVOD_TPU_WITH_NATIVE"):
                    raise RuntimeError(
                        f"native runtime build failed and "
                        f"HOROVOD_TPU_WITH_NATIVE is set: {e}") from e
                print(f"warning: native runtime build failed ({e}); "
                      f"falling back to pure-Python runtime",
                      file=sys.stderr)
        super().run()


class build_native_cmd(Command):
    """`python setup.py build_native` - just the .so."""

    description = "build the native runtime library"
    user_options = []

    def initialize_options(self):
        pass

    def finalize_options(self):
        pass

    def run(self):
        build_native()


setup(
    name="horovod_tpu",
    version="0.1.0",
    description=("TPU-native distributed training framework with the "
                 "capabilities of Horovod: named collectives, "
                 "DistributedOptimizer, tensor fusion, timeline, plus "
                 "dp/tp/sp/pp/ep parallelism over JAX/XLA/Pallas"),
    packages=find_packages(include=["horovod_tpu", "horovod_tpu.*"]),
    package_data={"horovod_tpu.native": ["*.cc", "*.h", "Makefile",
                                         "libhvdtpu.so"]},
    python_requires=">=3.10",
    install_requires=["jax>=0.9", "numpy"],
    extras_require={
        "models": ["flax", "optax"],
        "torch": ["torch>=2.1"],
        "test": ["pytest"],
    },
    cmdclass={"build_py": build_py_with_native,
              "build_native": build_native_cmd},
)

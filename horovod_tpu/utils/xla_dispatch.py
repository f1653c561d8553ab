"""XLA executable-launch counting (dispatch-count instrumentation).

The data-plane megakernel work (ops/megakernel.py) collapses the
per-tensor eager choreography of a fused collective cycle into one
compiled launch per fusion group.  That property regresses silently —
one stray ``jnp.reshape`` on the drain thread and the steady state is
back to N dispatches — so it is asserted, not assumed: this module
counts *real* loaded-executable launches at jax's single dispatch choke
point (``pxla.ExecuteReplicated.__call__`` executes every compiled
program: jitted functions AND each eagerly-dispatched primitive), and
the megakernel executor + the regression tests in
tests/test_megakernel.py read the counts.

The patch is installed lazily and only when counting is enabled
(``HVD_TPU_COUNT_DISPATCHES=1`` — set by tests/conftest.py for the
whole tier-1 suite and by the dataplane bench); production runs never
pay the per-dispatch bookkeeping.  Scopes come in two flavors:

* ``record()`` — thread-local: counts only launches issued by the
  calling thread while the scope is open.  Used by the megakernel
  executor to attribute dispatches to one response execution even
  while user threads concurrently classify/place inputs.
* ``record(all_threads=True)`` — global: counts every launch in the
  process.  Used by the tests to count a whole submit→drain→
  synchronize cycle, wherever the drain happens to run.
"""

from __future__ import annotations

import contextlib
import os
import threading
from dataclasses import dataclass
from typing import List

_tls = threading.local()
_global_scopes: List["DispatchScope"] = []
_install_lock = threading.Lock()
_installed = False


def counting_enabled() -> bool:
    return os.environ.get("HVD_TPU_COUNT_DISPATCHES", "0") == "1"


@dataclass
class DispatchScope:
    """One open counting window; ``count`` is the number of XLA
    executable launches observed since the scope opened."""

    count: int = 0
    all_threads: bool = False


def _bump() -> None:
    for scope in getattr(_tls, "scopes", ()):  # thread-local windows
        scope.count += 1
    if _global_scopes:
        # Benign cross-thread increment race (GIL-serialized bytecode
        # makes torn counts impossible; at worst two racing launches
        # both land) — a test opens exactly one global scope at a
        # time around an otherwise-quiet process.
        for scope in _global_scopes:
            scope.count += 1


def install() -> bool:
    """Patch the dispatch choke point once.  Returns False when this
    jax version has no recognizable choke point (counting becomes a
    no-op rather than an import error)."""
    global _installed
    with _install_lock:
        if _installed:
            return True
        try:
            from jax._src.interpreters import pxla
        except Exception:  # noqa: BLE001 — jax internals moved
            return False
        target = getattr(pxla, "ExecuteReplicated", None)
        orig = getattr(target, "__call__", None)
        if orig is None:
            return False

        def counted_call(self, *args, **kwargs):
            _bump()
            return orig(self, *args, **kwargs)

        target.__call__ = counted_call
        _installed = True
        return True


@contextlib.contextmanager
def exact_scope():
    """Make EVERY dispatch visible to :func:`record` while open.

    jax's C++ pjit fastpath executes warm calls without touching any
    Python frame, so the patched choke point only sees cold (first)
    launches.  This scope disables fastpath *population* — patching
    ``pjit._get_fastpath_data`` to return None makes the C++ wrapper
    fall back to the Python dispatch path on every call — and clears
    the global C++ PjitFunction caches so previously-warmed functions
    re-enter through it too.  Strictly a measurement mode (the tests'
    dispatch counting): warm dispatch
    gets slower while open, results are unchanged.  On exit the
    fastpath is restored (and the caches cleared again so the
    no-fastpath entries cannot linger).
    """
    try:
        from jax._src import pjit as _pjit_mod
    except Exception:  # noqa: BLE001 — jax internals moved
        yield
        return
    orig = getattr(_pjit_mod, "_get_fastpath_data", None)
    caches = [getattr(_pjit_mod, n, None)
              for n in ("_cpp_pjit_cache_fun_only",
                        "_cpp_pjit_cache_explicit_attributes")]
    if orig is None:
        yield
        return

    def _clear_caches():
        for c in caches:
            try:
                c.clear()
            except Exception:  # noqa: BLE001
                pass

    _pjit_mod._get_fastpath_data = lambda *a, **k: None
    _clear_caches()
    try:
        yield
    finally:
        _pjit_mod._get_fastpath_data = orig
        _clear_caches()


@contextlib.contextmanager
def record(all_threads: bool = False):
    """Open a counting window; yields a :class:`DispatchScope` whose
    ``count`` is live while the window is open and final after."""
    scope = DispatchScope(all_threads=all_threads)
    if not install():
        yield scope  # unpatchable jax: counts stay 0 (callers tolerate)
        return
    if all_threads:
        _global_scopes.append(scope)
    else:
        scopes = getattr(_tls, "scopes", None)
        if scopes is None:
            scopes = _tls.scopes = []
        scopes.append(scope)
    try:
        yield scope
    finally:
        if all_threads:
            _global_scopes.remove(scope)
        else:
            _tls.scopes.remove(scope)

"""Compile-cache counters read through ``jax.monitoring`` (the benchmark's
copy of what chip_smoke.py counts).  ``mark()`` splits set-up from the
measured window: a compile request after the mark is a compilation inside
the window, and there should be none."""

from __future__ import annotations

_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "requests",
    "/jax/compilation_cache/cache_hits": "hits",
    "/jax/compilation_cache/cache_misses": "misses",
}


class CacheCounter:
    def __init__(self) -> None:
        self.total = {"requests": 0, "hits": 0, "misses": 0}
        self._at_mark = None
        self._at_end = None

    def install(self) -> "CacheCounter":
        import jax

        jax.monitoring.register_event_listener(self._on_event)
        return self

    def _on_event(self, name: str, **kw) -> None:
        key = _EVENTS.get(name)
        if key is not None:
            self.total[key] += 1

    def mark(self) -> None:
        self._at_mark = dict(self.total)

    def end(self) -> None:
        self._at_end = dict(self.total)

    @property
    def setup(self) -> dict:
        return dict(self._at_mark or self.total)

    @property
    def window(self) -> dict:
        if self._at_mark is None:
            return {k: 0 for k in self.total}
        end = self._at_end or self.total
        return {k: end[k] - self._at_mark[k] for k in end}

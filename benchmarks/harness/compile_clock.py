"""XLA compile accounting through ``jax.monitoring`` (copied from
``chip_smoke.py``'s CompileClock, with the time of each request kept so that
the compiles between two ticks can be counted in every run)."""

import os
import sys
import time


class CompileClock:
    """Every backend compile (an XLA compile or a persistent-cache load: both
    sit inside the backend_compile event) with its time, seconds and the
    innermost ``arroyo_tpu`` frame on the compiling thread's stack (the
    jitted kernels share one name, so the call site names them)."""

    def __init__(self):
        import jax.monitoring as mon

        self.compiles = []  # (monotonic end time, seconds, call site)
        self.requests = self.hits = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, name, secs, **_kw):
        if name != "/jax/core/compile/backend_compile_duration":
            return
        f = sys._getframe()
        while f is not None and (
                "/arroyo_tpu/" not in f.f_code.co_filename
                or f.f_code.co_filename.endswith("obs/perf.py")):
            f = f.f_back
        site = ("(outside arroyo_tpu)" if f is None else
                f"{os.path.basename(f.f_code.co_filename)}:"
                f"{f.f_code.co_name}")
        self.compiles.append((time.monotonic(), secs, site))

    def _on_event(self, name, **_kw):
        if name == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif name == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def between(self, t0, t1):
        """(count, seconds, {site: [count, seconds]}) of the compiles that
        ended in (t0, t1]."""
        sites = {}
        for at, secs, site in self.compiles:
            if t0 < at <= t1:
                entry = sites.setdefault(site, [0, 0.0])
                entry[0] += 1
                entry[1] += secs
        return (sum(c for c, _ in sites.values()),
                sum(s for _, s in sites.values()), sites)

"""What the harness itself observes of a run: compilations (JAX's own
monitoring events and the program's compile tracker), the program's counters,
and, in a ``--trace 1`` run, a profiler trace of one slice of the window.

Only the process that holds the chip can trace it, so the trace is taken here,
around whole operations of the driver (``Tracer.slice``), and reduced by
``harness/trace.py``.
"""

from __future__ import annotations

import contextlib
import shutil
import time
from pathlib import Path


def registry_counters() -> dict[str, float]:
    """Every counter and gauge of the program's registry, by name."""
    from reth_tpu.metrics import REGISTRY

    out = {}
    for name, metric in dict(REGISTRY.items()).items():
        v = getattr(metric, "value", None)
        if isinstance(v, (int, float)):
            out[name] = float(v)
    return out


class Tracer:
    def __init__(self, enabled: bool, out_dir: Path):
        import jax

        self.enabled = enabled
        self.out_dir = Path(out_dir)
        self._compiled = 0
        self._hits = 0
        self._slice: dict | None = None
        self._reduced: dict | None = None
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, name: str, **kw) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            self._hits += 1

    def _on_duration(self, name: str, secs: float, **kw) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            self._compiled += 1

    def compile_counts(self) -> dict[str, int]:
        """Programs built so far, by JAX's own events: ``compiled`` by the
        backend, ``cache_hits`` loaded from the persistent cache (a load
        stalls the caller too, some 0.4 s a program on a v5e), ``programs``
        both together; and distinct shapes the program's tracker has seen."""
        from reth_tpu.metrics import compile_tracker

        return {"compiled": self._compiled - self._hits,
                "cache_hits": self._hits,
                "programs": self._compiled,
                "shapes": len(compile_tracker.shapes)}

    @contextlib.contextmanager
    def slice(self, span: str):
        """Profile what runs inside: whole operations of the driver, wrapped
        by it in ``TraceAnnotation(span)``. One slice to a run."""
        if not self.enabled or self._slice is not None:
            yield
            return
        import jax

        shutil.rmtree(self.out_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(self.out_dir), profiler_options=opts)
        t0 = time.perf_counter()
        try:
            yield
        finally:
            wall = time.perf_counter() - t0
            jax.profiler.stop_trace()
            self._slice = {"span": span, "wall_s": wall}

    def reduced(self) -> dict | None:
        """The slice's trace reduced to busy time, idle gaps and the top
        operations (``harness/trace.py``); None where nothing was traced."""
        if self._slice is None:
            return None
        if self._reduced is None:
            from . import trace

            files = sorted(self.out_dir.glob("plugins/profile/*/*.xplane.pb"))
            if not files:
                return None
            events = trace.read_xplane(files[-1])
            self._reduced = trace.reduce_events(events, self._slice["span"])
            self._reduced["xplane_bytes"] = files[-1].stat().st_size
            shutil.rmtree(self.out_dir, ignore_errors=True)
        return self._reduced

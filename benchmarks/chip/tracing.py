"""Profiler traces of a steady part of a window, and the benchmark's own
host spans on the profiler's clock."""
from __future__ import annotations

import shutil
import threading
from pathlib import Path
from typing import Any, Dict, Optional

from benchmarks.chip import trace_reduce


def span(name: str):
    """A host span on the profiler's clock; costs about a microsecond
    while no trace is being taken."""
    import jax

    return jax.profiler.TraceAnnotation(f"bench/{name}")


class Tracer:
    """Start and stop one trace (Python's own tracer off, so the trace
    holds device work and host spans), then reduce it and delete it."""

    def __init__(self, log_dir: Path) -> None:
        self.log_dir = Path(log_dir)
        self._span = None

    def start(self) -> None:
        import jax

        shutil.rmtree(self.log_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(self.log_dir), profiler_options=opts)
        self._span = span("traced")
        self._span.__enter__()

    def stop(self) -> None:
        import jax

        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def reduce(self) -> Dict[str, Any]:
        try:
            return trace_reduce.reduce_trace(
                trace_reduce.load_xplane(str(self.log_dir)))
        finally:
            shutil.rmtree(self.log_dir, ignore_errors=True)


class TimedTrace:
    """Trace ``[start_s, start_s + length_s]`` after :meth:`arm`, from a
    thread of its own, for a window whose loop is not the benchmark's."""

    def __init__(self, tracer: Tracer, start_s: float, length_s: float):
        self.tracer = tracer
        self.start_s, self.length_s = start_s, length_s
        self._thread: Optional[threading.Thread] = None
        self._cancel = threading.Event()
        self.error: Optional[BaseException] = None
        self.taken = False

    def arm(self) -> None:
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self) -> None:
        try:
            if self._cancel.wait(self.start_s):
                return
            self.tracer.start()
            self._cancel.wait(self.length_s)
            self.tracer.stop()
            self.taken = True
        except BaseException as e:  # noqa: BLE001 - re-raised by join()
            self.error = e

    def join(self) -> None:
        self._cancel.set()
        self._thread.join()
        if self.error is not None:
            raise self.error


def trace_plan(seconds: float):
    """Where in a window of ``seconds`` the trace starts, and how long it
    runs: past the first quarter, at most four seconds."""
    return 0.25 * seconds, min(4.0, 0.5 * seconds)

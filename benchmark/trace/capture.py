"""Taking the trace: a few seconds of the window, in a run of its own
(`--trace 1`). The trace is written under TMPDIR, reduced, and removed."""
from __future__ import annotations

import contextlib
import glob
import os
import shutil
import tempfile

from . import reduce as _reduce

# the benchmark's own host spans, around its calls into each layer
SPANS = ("submit", "router.step", "stamp", "wait", "next_batch", "step",
         "sync")


def span(name: str):
    """A host span on the profiler's clock (free when no trace runs)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


class Capture:
    """start() ... stop() -> the reduction (or None). With enabled=False
    both do nothing, so the runners call them unconditionally."""

    def __init__(self, enabled: bool):
        self.enabled = bool(enabled)
        self.running = False
        self._dir = None
        self._mark = None

    def start(self) -> None:
        if not self.enabled or self.running:
            return
        import jax
        self._dir = tempfile.mkdtemp(prefix="bench-trace-")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self._dir, profiler_options=options)
        self._mark = contextlib.ExitStack()
        self._mark.enter_context(span(_reduce.WINDOW_SPAN))
        self.running = True

    def stop(self):
        if not self.running:
            return None
        import jax
        self._mark.close()
        jax.profiler.stop_trace()
        self.running = False
        try:
            files = glob.glob(os.path.join(self._dir, "**", "*.xplane.pb"),
                              recursive=True)
            if not files:
                return None
            return _reduce.reduce(_reduce.load(files[0]), SPANS)
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)

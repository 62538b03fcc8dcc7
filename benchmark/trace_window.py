"""Traces a slice of the measured window with JAX's profiler, from a side
thread, while the load generator keeps the main thread. Only the process that
holds the chip can trace it, which is why the benchmark drives the scheduler
in-process."""

from __future__ import annotations

import glob
import os
import shutil
import threading
import time


class TraceWindow(threading.Thread):
    def __init__(self, out_dir: str, *, start_after_s: float, seconds: float):
        super().__init__(daemon=True)
        self.out_dir = out_dir
        self.start_after_s = start_after_s
        self.seconds = seconds
        self.window_s = 0.0
        self.error: str | None = None

    def run(self) -> None:
        import jax

        shutil.rmtree(self.out_dir, ignore_errors=True)
        os.makedirs(self.out_dir, exist_ok=True)
        time.sleep(self.start_after_s)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # no per-call Python events: they slow the host
        opts.host_tracer_level = 2       # keep TraceAnnotation spans
        try:
            jax.profiler.start_trace(self.out_dir, profiler_options=opts)
            t0 = time.monotonic()
            time.sleep(self.seconds)
            self.window_s = time.monotonic() - t0
            jax.profiler.stop_trace()
        except Exception as e:  # noqa: BLE001 — reported in the result, the run goes on
            self.error = f"{type(e).__name__}: {e}"

    def finish(self) -> dict | None:
        self.join(600.0)
        files = glob.glob(os.path.join(self.out_dir, "**", "*.xplane.pb"), recursive=True)
        if self.error or not files:
            raise RuntimeError(f"the profiler left no trace ({self.error or 'no .xplane.pb'})")
        return {"path": max(files, key=os.path.getmtime), "seconds": self.window_s}

"""What the host did to a window, so that a run that reads far off says why.

Two cheap readings, printed on the ``host:`` line: the longest oversleep of a
thread that sleeps 20 ms at a time (every thread of the process was held that
long, by another tenant of the machine's cores or by the interpreter's lock)
with the moment it happened, and the seconds Python's garbage collector ran.
(The chip tool's machine shows no context-switch counts and no
``/proc/pressure``.)  Neither is a metric; they are evidence for PERF.md.
"""
from __future__ import annotations

import gc
import threading
import time

NAP_S = 0.02


class HostWatch:
    def __enter__(self):
        self.t0 = time.perf_counter()
        self._stop = threading.Event()
        self.worst = (0.0, 0.0)          # (oversleep, seconds into the window)
        self.over_100ms = 0
        self.gc_s, self._gc_t = 0.0, None
        self.gc_runs = 0
        gc.callbacks.append(self._on_gc)
        self._thread = threading.Thread(target=self._nap, daemon=True)
        self._thread.start()
        return self

    def _on_gc(self, phase, _info):
        if phase == "start":
            self._gc_t = time.perf_counter()
        elif self._gc_t is not None:
            self.gc_s += time.perf_counter() - self._gc_t
            self.gc_runs += 1
            self._gc_t = None

    def _nap(self):
        while not self._stop.is_set():
            t = time.perf_counter()
            time.sleep(NAP_S)
            over = time.perf_counter() - t - NAP_S
            if over > 0.1:
                self.over_100ms += 1
            if over > self.worst[0]:
                self.worst = (over, t - self.t0)

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join()
        gc.callbacks.remove(self._on_gc)
        self.report = {
            "oversleep_max_s": round(self.worst[0], 4),
            "oversleep_max_at_s": round(self.worst[1], 2),
            "oversleeps_over_100ms": self.over_100ms,
            "gc_s": round(self.gc_s, 4), "gc_runs": self.gc_runs}
        return False

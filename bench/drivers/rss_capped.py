"""The one-chip closed loop under a ceiling on the process's host memory.

The traffic file gives the ceiling as ``host_rss_ceiling_gb`` (GiB). A
watch thread reads the process's resident set every ``POLL_S`` seconds
from the moment the driver is made, so through building the operands, the
warm-up compiles and the window alike. As soon as the resident set passes
the ceiling it logs the reading and ends the process with exit code
``EXIT_CODE``: a program whose host memory grows without bound (a
compile that unrolls with the stream, say) then fails at once and
cleanly, instead of being killed by the machine when its memory runs out.
``close`` logs the peak resident set of the run (``ru_maxrss``; some
kernels' ``/proc/self/status`` has no ``VmHWM``).
"""
from __future__ import annotations

import os
import resource
import threading

import harness

_closed_loop = harness.load_module(harness.BENCH / "drivers"
                                   / "closed_loop.py")

CHIPS = _closed_loop.CHIPS
POLL_S = 0.2
EXIT_CODE = 3
GIB = 1 << 30


def rss_bytes() -> int:
    """The process's resident set now (``VmRSS`` of ``/proc/self/status``),
    in bytes."""
    with open("/proc/self/status") as f:
        for line in f:
            if line.startswith("VmRSS:"):
                return int(line.split()[1]) * 1024
    raise KeyError("VmRSS")


def peak_rss_bytes() -> int:
    """The process's largest resident set so far, in bytes."""
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024


class Driver(_closed_loop.Driver):

    def __init__(self, traffic: dict):
        self.ceiling = float(traffic["host_rss_ceiling_gb"]) * GIB
        self._stop = threading.Event()
        self._watch = threading.Thread(target=self._run, daemon=True,
                                       name="rss-ceiling")
        self._watch.start()
        super().__init__(traffic)

    def _run(self) -> None:
        while not self._stop.wait(POLL_S):
            rss = rss_bytes()
            if rss > self.ceiling:
                self.trip(rss)
                return

    def trip(self, rss: int) -> None:
        """End the run: the resident set ``rss`` passed the ceiling."""
        harness.log(f"[rss] host RSS {rss / GIB:.2f} GiB passed the ceiling "
                    f"of {self.ceiling / GIB:.2f} GiB; run ended")
        os._exit(EXIT_CODE)

    def close(self) -> None:
        self._stop.set()
        self._watch.join(timeout=10 * POLL_S)
        harness.log(f"[rss] peak host RSS {peak_rss_bytes() / GIB:.2f} GiB "
                    f"(ceiling {self.ceiling / GIB:.2f} GiB)")
        super().close()

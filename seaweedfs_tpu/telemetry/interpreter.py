"""How long a runnable thread of this process waits for the interpreter.

Every hand-over between the threads of a server — event loop -> worker,
worker -> gather pool, a requester -> the codec service's scheduler and
back — ends with the woken thread waiting for the one interpreter lock.
No span can see that wait: it sits between a span's end on one thread and
the next span's start on another.  A probe thread that sleeps a fixed
period and then observes how late it woke measures it directly, as any
other thread woken at that moment would have paid it.
"""

from __future__ import annotations

import threading
import time

from ..stats.metrics import INTERPRETER_WAIT

# 100 samples a second: a p95 over a phase of seconds rests on hundreds of
# them (a 20 s window holds 2,000), for one interpreter acquisition and one
# histogram observation every 10 ms.
PERIOD_S = 0.010


def _run() -> None:
    child = INTERPRETER_WAIT.labels()
    while True:
        t0 = time.perf_counter()
        time.sleep(PERIOD_S)
        child.observe(max(0.0, time.perf_counter() - t0 - PERIOD_S))


def start_wait_probe() -> threading.Thread:
    """Start the probe: a daemon thread, so it ends with its process.
    A server process calls it once at start (cli `server` / `volume`)."""
    t = threading.Thread(target=_run, name="interpreter-wait-probe",
                         daemon=True)
    t.start()
    return t

"""One client, closed loop: each request is sent when the answer to the one
before has come back.

The traffic's ``warmup`` requests go in set-up.  The window sends the
requests after them and ends at the first answer after ``--seconds``; every
answer is a unit of the window, with the time it took, the fixpoint passes
it ran and the ops it carried.  A request that raises counts as failed and
ends the window.
"""
from __future__ import annotations

import time


def warm_up(run, service) -> None:
    for i in range(int(run.traffic.get("warmup", 0))):
        service.request(run, run.requests.request(i))


def window(run, service) -> None:
    i = int(run.traffic.get("warmup", 0))
    t0 = time.perf_counter()
    while True:
        run.attempted += 1
        req = run.requests.request(i)
        ts = time.perf_counter()
        try:
            reply = service.request(run, req)
        except Exception as e:  # a refused or failed request ends the window
            run.fail(e)
            break
        te = time.perf_counter()
        service.observe(run, reply)
        run.units.append({"name": service.UNIT, "start": ts, "end": te,
                          "passes": reply["passes"], "ops": reply["ops"]})
        i += 1
        if te - t0 >= run.seconds:
            break
    run.window_s = time.perf_counter() - t0

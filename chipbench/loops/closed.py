"""Closed loop: one caller sends request after request, each as soon as
the previous one's answers are on the host, until ``seconds`` have
passed since the first.  A request is timed from when it was sent.
Requests are taken from the plan's pool in order."""
from __future__ import annotations

import time

KEYS = frozenset()   # no mix keys of its own


def run(system, plan, seconds: float, rec, sampler):
    if plan.callers != 1:
        raise ValueError("the closed loop drives exactly one caller")
    served = []
    t0 = time.perf_counter()
    i = 0
    while time.perf_counter() - t0 < seconds:
        t_sent = time.perf_counter()
        with rec.span("request"):
            ids, counts = system.serve(i, rec)
        t_done = time.perf_counter()
        served.append((i, t_sent, t_done, counts))
        sampler.offer(i, ids, counts)
        i += 1
    return t0, served

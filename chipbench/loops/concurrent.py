"""Concurrent closed loop: ``callers`` callers in one thread, each
sending its next request as soon as the previous one's answers are on
the host, through the system's scheduler.  Every caller submits, then
one drain serves every queued request (the scheduler coalesces them
into one batch) and the answered callers submit again, until
``seconds`` have passed since the first send; the requests still in
flight are then drained.  A request is timed from its submit to the
end of the drain that answered it.  Requests are taken from the plan's
pool in order.

Each drain is one ``chipbench.request`` span (a round of callers, not
one request), so a trace's ``n_requests`` counts rounds.

The system provides ``submit(i) -> uid`` and ``drain() -> {uid: (ids
per row, counts)}``.  ``repeat_share`` is the share of the pool's
requests that resubmit an earlier one (the system's ``inputs`` draws
the pool with them).
"""
from __future__ import annotations

import time

KEYS = frozenset({"repeat_share"})


def run(system, plan, seconds: float, rec, sampler):
    served = []
    sent = {}            # uid -> (request, time sent)
    nxt = 0

    def send():
        nonlocal nxt
        sent[system.submit(nxt)] = (nxt, time.perf_counter())
        nxt += 1

    t0 = time.perf_counter()
    for _ in range(plan.callers):
        send()
    while sent:
        with rec.span("request"):
            answers = system.drain()
        t_done = time.perf_counter()
        done = sorted((sent.pop(uid), ans) for uid, ans in answers.items())
        for (i, t_sent), (ids, counts) in done:
            served.append((i, t_sent, t_done, counts))
            sampler.offer(i, ids, counts)
        if t_done - t0 < seconds:
            for _ in done:
                send()
    return t0, served

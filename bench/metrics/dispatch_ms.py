"""Host time to enqueue one wave's eager forward, in milliseconds.

Mean over the window's waves of the executor's own stamps
(WaveTiming.dispatch_s - start_s, core/executor.PhaseRun.dispatch): the
time from the wave's shared input being ready to its forward being
dispatched, on the host clock.
"""


def read(rec):
    d = [w[1] - w[0] for p in rec.phases for w in p.waves]
    return 1000.0 * sum(d) / len(d) if d else None

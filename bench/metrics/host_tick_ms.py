"""Engine host: host time of one engine tick that is not spent waiting for
the device.  From the program's spans in the trace: each ``serving.step``
that lies wholly inside the window, less the ``serving.*.wait`` spans
inside it (the host blocked on a device result); the median over those
ticks.  What is left is planning, admission, input building, dispatch
and emitting tokens: the host work that keeps the device idle between
programs."""
import bisect
import statistics

STEP = "serving.step"


def read(run):
    tr = run.trace
    steps = [s for s in tr.spans(lambda n: n == STEP)
             if tr.t0 < s.start and s.start + s.dur < tr.t1]
    if not steps:
        return None
    waits = tr.spans(lambda n: n.startswith("serving.")
                     and n.endswith(".wait"))
    starts = [w.start for w in waits]
    host = []
    for s in steps:
        i = bisect.bisect_left(starts, s.start)
        j = bisect.bisect_right(starts, s.start + s.dur)
        host.append(s.dur - sum(w.dur for w in waits[i:j]))
    return 1e3 * statistics.median(host)

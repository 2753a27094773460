"""Mean device-idle time between consecutive runs of the serving round
program, in ms: the host path's admission, harvest and scheduling.  Idle
time while the benchmark waited for the next arrival (``bench.wait``) is
the traffic's, not the host path's, and is left out."""

ROUND = "round_fn"


def read(r):
    t = r.trace
    if t is None:
        return None
    rounds = [m for m in t.devices[0].modules if ROUND in m[0]]
    if len(rounds) < 2:
        return None
    gaps = t.idle_gaps()
    total = 0.0
    for (_, _, end), (_, start, _) in zip(rounds, rounds[1:]):
        for s, e, label in gaps:
            if label != "bench.wait":
                total += max(0.0, min(e, start) - max(s, end))
    return total * 1e-6 / (len(rounds) - 1)

"""Seconds of queueing in the window per request that arrived in it, from
the serving engine's own counters: the window's change of ``queue_wait_s``
(the wait of each request admitted, summed at admission) plus that of
``waiting_s`` (the wait so far of the requests still queued).  Together
they count the queue's wait inside the window and nothing before it, so a
request still queued at the window's end counts as far as it waited, and a
lead-in request admitted in the window counts only its wait there: by
Little's law the reading is the mean queue length times the window over the
arrivals, and it rises when the server falls behind."""


def read(r):
    c = r.counters
    arrivals = c.get("requests")
    if "window_queue_wait_s" not in c or "window_waiting_s" not in c or not arrivals:
        return None
    return (c["window_queue_wait_s"] + c["window_waiting_s"]) / arrivals

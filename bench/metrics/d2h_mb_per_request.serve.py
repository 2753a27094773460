"""Megabytes the serving engine's harvest read back from the device per
result it returned in the window, from its own counters (``d2h_bytes``
over ``harvested``): the polls of each lane's age and contract, and the
iterates fetched when a lane finishes."""


def read(r):
    c = r.counters
    harvested = c.get("window_harvested")
    if "window_d2h_bytes" not in c or not harvested:
        return None
    return c["window_d2h_bytes"] / 1e6 / harvested

"""Slot iterations stepped over slot iterations offered in the window's
rounds (rounds x slots x round_iters), in percent, from the serving
engine's own counters."""


def read(r):
    c = r.counters
    offered = c.get("window_rounds", 0) * c.get("slots", 0) * c.get("round_iters", 0)
    if not offered:
        return None
    return 100.0 * c["window_slot_iters"] / offered

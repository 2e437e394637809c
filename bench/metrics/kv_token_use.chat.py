"""How much of the KV cache that admission holds is in use: over the
window's decode steps, the tokens held in the cache by the active slots
(the program's ``tokens_held`` counter) over the tokens their pages and the
pool's reservations for them could hold (``token_capacity``), in %.  None
where the program records no such counters or lost some in the window."""


def read(r):
    try:
        from repro import tracing
    except ImportError:
        return None
    recs = tracing.records(since=r.window[0], until=r.window[1])
    steps = [x.attrs for x in recs if x.name == "serving.decode"]
    cap = sum(a.get("token_capacity", 0) for a in steps)
    if recs.dropped or cap <= 0:
        return None
    return 100.0 * sum(a.get("tokens_held", 0) for a in steps) / cap

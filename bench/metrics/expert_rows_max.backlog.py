"""How far the busiest expert stands above an even load: over the
window's decode steps, the mean of the most rows one expert took in one
layer (the program's ``expert_rows_max``) over the rows an expert takes
when every expert of every layer takes as many (``routed_rows`` / (layers
x experts)).  1 is an even load.  None where the program records no such
counters or lost some in the window."""


def read(r):
    try:
        from repro import tracing
    except ImportError:
        return None
    recs = tracing.records(since=r.window[0], until=r.window[1])
    steps = [x.attrs for x in recs
             if x.name == "serving.decode" and "expert_rows_max" in x.attrs]
    if recs.dropped or not steps:
        return None
    cells = r.config["num_hidden_layers"] * r.config["num_experts"]
    return sum(a["expert_rows_max"] / (a["routed_rows"] / cells)
               for a in steps) / len(steps)

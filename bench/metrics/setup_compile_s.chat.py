"""Seconds of set-up the serving engine spent compiling: tracing, lowering,
backend compiles and persistent-cache reads (the program's ``compile_s``)
of its ``serving.*`` spans that ended before the window opened.  None where
the program records no such spans or lost some of them."""


def read(r):
    try:
        from repro import tracing
    except ImportError:
        return None
    recs = tracing.records(until=r.window[0])
    spans = [x for x in recs if x.name.startswith("serving.")]
    if recs.dropped or not spans:
        return None
    return sum(x.attrs.get("compile_s", 0.0) for x in spans)

"""How long a prefill holds up the decode steps: for each gap between the
ends of consecutive ``serving.decode`` spans of the program that holds one
or more ``serving.prefill`` spans, those prefills' summed duration; the
median over such gaps, in ms.  None where the program records no such
spans."""
import bisect

from harness.serving import percentile


def read(r):
    ends = sorted(e for n, _, e in r.trace.host if n == "serving.decode")
    pre = sorted((s, e) for n, s, e in r.trace.host if n == "serving.prefill")
    starts = [s for s, _ in pre]
    stalls = []
    for a, b in zip(ends, ends[1:]):
        held = pre[bisect.bisect_left(starts, a):bisect.bisect_left(starts, b)]
        if held:
            stalls.append(sum(e - s for s, e in held) / 1e6)
    return percentile(stalls, 50) if stalls else None

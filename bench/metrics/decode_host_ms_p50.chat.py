"""Host time of the engine's decode step: for each ``serving.decode`` span
of the program in the traced window, the time inside it in which no op ran
on chip 0; the median over the steps, in ms.  None where the program
records no such span."""
import bisect

from harness.serving import percentile


def read(r):
    steps = [(s, e) for n, s, e in r.trace.host if n == "serving.decode"]
    busy = r.trace.busy.get(0, [])
    if not steps:
        return None
    starts = [s for s, _ in busy]
    idle = []
    for s, e in steps:
        covered = 0
        i = max(bisect.bisect_right(starts, s) - 1, 0)
        while i < len(busy) and busy[i][0] < e:
            covered += max(0, min(e, busy[i][1]) - max(s, busy[i][0]))
            i += 1
        idle.append((e - s - covered) / 1e6)
    return percentile(idle, 50)

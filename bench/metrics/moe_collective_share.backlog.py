"""The collectives left exposed on chip 0, in % of its busy time: the
seconds in which a collective (the expert combine's psum, the QK-norm and
vocabulary reductions) ran on the chip and nothing else did, over the
seconds in which anything ran there.  None where chip 0 ran nothing."""
from harness.trace import total


def read(r):
    busy = total(r.trace.busy.get(0, [])) / 1e9
    if busy <= 0:
        return None
    return 100.0 * r.trace.exposed_collective_s(chip=0) / busy

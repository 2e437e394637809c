"""Median wait of a request in the scheduler's queue, from when it was due
to its admission, over the window's requests (harness timestamps)."""
from harness.serving import percentile


def read(r):
    waits = r.counts.get("queue_wait_ms")
    return percentile(waits, 50) if waits else None

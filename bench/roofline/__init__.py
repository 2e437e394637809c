"""Operation and byte counts of kernels and models, from their shapes, and
the peaks they are held to (``peaks.json``, keyed by device kind)."""

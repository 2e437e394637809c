"""Share of the traced window in which no op ran on the chips, averaged
over the cell's four (the chat cell's reading, on this cell)."""
from harness.metrics import idle_share as read  # noqa: F401

"""Share of the traced window in which no op ran on the chip."""
from harness.metrics import idle_share as read  # noqa: F401

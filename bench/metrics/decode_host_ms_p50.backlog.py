"""Host time of the engine's decode step on chip 0: the chat cell's
reading (``decode_host_ms_p50.chat.py``), on this cell's traced window.
None where the program records no ``serving.decode`` span."""
from harness import core

read = core.load_module(core.BENCH / "metrics" /
                        "decode_host_ms_p50.chat.py").read

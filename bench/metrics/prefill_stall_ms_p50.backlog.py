"""How long the admissions' prefills hold up the decode steps: the chat
cell's reading (``prefill_stall_ms_p50.chat.py``), on this cell's traced
window, where each gap between decode steps may hold several B=1 prefills.
None where the program records no such spans."""
from harness import core

read = core.load_module(core.BENCH / "metrics" /
                        "prefill_stall_ms_p50.chat.py").read

"""Seconds of set-up the sharded serving engine spent compiling: the chat
cell's reading (``setup_compile_s.chat.py``) on this cell.  None where the
program records no ``serving.*`` spans or lost some of them."""
from harness import core

read = core.load_module(core.BENCH / "metrics" /
                        "setup_compile_s.chat.py").read

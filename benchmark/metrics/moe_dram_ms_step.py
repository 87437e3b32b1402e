"""`ph_dram_ms_step` (device milliseconds per step under the scope
`s.dram`) in the expert layer's cell, where the memory controllers'
FIFO rank runs over 16384 lanes into 4096 queues. The reader is
`ph_dram_ms_step.py`'s, whose closed list of cells a PR that adds a cell
may not open."""

import os

# the checkout this file was loaded from: its sibling is that checkout's
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def read(run, trace):
    import cells

    return cells.load_metric("ph_dram_ms_step", ROOT)(run, trace)

"""`inval_fanout` (invalidation messages per write that reached the
directory, of the checked job) in the expert layer's cell: what a store to
an expert's scratch pays for the group that read it, under the coarse
vector 64 messages a flagged group. The reader is `inval_fanout.py`'s,
whose closed list of cells a PR that adds a cell may not open."""

import os

# the checkout this file was loaded from: its sibling is that checkout's
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def read(run, trace):
    import cells

    return cells.load_metric("inval_fanout", ROOT)(run, trace)

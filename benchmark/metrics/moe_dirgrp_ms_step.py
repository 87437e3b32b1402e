"""`ph_dirgrp_ms_step` (device milliseconds per step under the scope
`s.dir/grp`, the coarse sharer vector's group work) in the expert layer's
cell, where a group is an expert. The reader is `ph_dirgrp_ms_step.py`'s,
whose closed list of cells a PR that adds a cell may not open."""

import os

# the checkout this file was loaded from: its sibling is that checkout's
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def read(run, trace):
    import cells

    return cells.load_metric("ph_dirgrp_ms_step", ROOT)(run, trace)

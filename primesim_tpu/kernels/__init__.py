"""Pallas TPU step-kernel subsystem (DESIGN.md §11).

The engine's step body is a serial chain of dozens of small XLA
gather/scatter kernels whose PER-KERNEL overhead — not bytes — sets the
~2.8 ms/step floor at 1024 cores (DESIGN.md §9 postscript). This package
fuses the dominant serial segments into a few VMEM-resident Pallas
kernels, selected by `MachineConfig.step_impl == "pallas"`:

- `step_kernels.probe_classify` — phase 1 + the LLC home-row parse: L1
  set probe, pointer validation, hit classification, sharer predicates
  and victim selection, one kernel over core blocks.
- `step_kernels.commit_step` — phase 4.A + the counter fold: the fused
  L1 writes, the directory row delta, and the stacked counter add.
- `reductions.sharer_reductions` — the dense invalidation /
  back-invalidation reductions.

`layouts.py` pins the shared block geometry (core-block size, plane and
directory-row column maps) and the Mosaic-safe select/reduce idioms all
three kernels are written in. Every kernel is bit-exact vs the XLA step
(tests/test_step_pallas.py); on the CPU it runs in interpreter mode, on
a TPU Mosaic compiles it, and any other platform is an error
(`layouts.interpret_mode`).
"""

from .layouts import core_block  # noqa: F401
from .reductions import sharer_reductions  # noqa: F401
from .step_kernels import commit_step, probe_classify  # noqa: F401

"""Share of the training step's device time spent in the numerics seam:
device self time of the ops under any ``seam.<site>`` named scope (every
matmul site, in every numerics mode), over the device's busy time in the
traced window, in percent."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness import program_trace  # noqa: E402


def read(ctx):
    pt = program_trace.of(ctx)
    if pt is None:
        return None
    return program_trace.scope_share(pt, lambda sc: sc.startswith("seam."))

"""Share of the traced window in which the device is idle while the serve
engine's host code runs: device-idle time whose innermost host span is one
of the engine's ``serve.*`` spans, over the window, averaged over the
cell's chips, in percent."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness import program_trace  # noqa: E402


def read(ctx):
    pt = program_trace.of(ctx)
    if pt is None:
        return None
    return program_trace.program_idle_share(pt)

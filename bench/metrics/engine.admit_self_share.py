"""Share of the serve engine's own time spent admitting requests: host
seconds under the engine's ``serve.admit`` spans (prefill, slot insert,
first-token fetch, each ending in a ``device_get``), over those plus the
seconds under its ``serve.decode`` spans, over the traced loop, in
percent."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness import program_trace  # noqa: E402


def read(ctx):
    pt = program_trace.of(ctx)
    if pt is None:
        return None
    admit = program_trace.span_seconds(pt, "serve.admit")
    decode = program_trace.span_seconds(pt, "serve.decode")
    if admit + decode <= 0:
        return None
    return 100.0 * admit / (admit + decode)

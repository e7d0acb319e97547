"""Shared environment-gating markers for the test suite."""
import os

import pytest

# Full conformance-matrix sweeps (every arch x every mode) are minutes of
# CPU — they run in the nightly workflow (REPRO_NIGHTLY=1), while tier-1
# keeps one representative arm per family.  An env gate rather than a
# pytest -m filter so the tier-1 invocation (`pytest -x -q`) needs no
# extra flags and can never accidentally pick the slow arms up.
nightly = pytest.mark.skipif(
    not os.environ.get("REPRO_NIGHTLY"),
    reason="nightly-only sweep (set REPRO_NIGHTLY=1)")

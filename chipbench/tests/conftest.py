import os
import sys

import pytest

# four CPU devices for the 2x2-mesh cell; set before JAX starts
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=4")

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import tiny  # noqa: E402


@pytest.fixture
def cpu_peaks(monkeypatch):
    """The CPU is not in the peaks table: give it made-up peaks, so the
    harness runs there; no number read with them is a device number."""
    from chipbench import roofline
    monkeypatch.setattr(roofline, "peaks_for", lambda d: tiny.CPU_PEAKS)

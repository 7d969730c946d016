"""Test harness: emulate an 8-chip slice on CPU.

Must run before jax is imported anywhere (SURVEY.md §4: multi-device tests via
``--xla_force_host_platform_device_count``).
"""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def eight_devices():
    import jax
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs


class TimedFakeEngine:
    """Shared deterministic fake engine with a real (wall-clock) per-task
    compute duration — the Node-contract fake for wall-clock scheduling/
    recovery tests (`infer` signature and result attributes match
    `idunno_tpu.engine.inference.InferenceEngine`)."""

    def __init__(self, work_s: float):
        self.work_s = work_s

    def infer(self, name, start, end, dataset_root=None):
        import time
        from types import SimpleNamespace
        time.sleep(self.work_s)
        return SimpleNamespace(
            records=[(f"test_{i}.JPEG", f"class_{i % 1000}", 0.9)
                     for i in range(start, end + 1)],
            elapsed_s=self.work_s, weights="random")

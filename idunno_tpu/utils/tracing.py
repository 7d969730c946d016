"""Profiler traces.

The reference has no tracer — its only timing is coarse host wall-clock
around the whole per-task loop (`alexnet_resnet.py:16,91-92`) and around
dispatch (`mp4_machinelearning.py:792-804`). On TPU, host wall-clock lies
twice over: dispatch is async (the Python call returns before the chip
runs) and the first call includes compilation. What remains here:

- ``trace``: context manager around ``jax.profiler`` emitting a TensorBoard
  trace directory for the wrapped region (XLA per-op device timeline).

The benchmark reads its traces itself (`benchmark/trace.py`); the program's
named regions are its spans (`utils/spans.py`, `engine/serve_lm.py:
loop_span`), each also a `jax.profiler.TraceAnnotation`.
"""
from __future__ import annotations

import contextlib

import jax


@contextlib.contextmanager
def trace(log_dir: str):
    """Profile the wrapped region into ``log_dir`` (TensorBoard/XPlane
    format, includes the XLA device timeline)."""
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


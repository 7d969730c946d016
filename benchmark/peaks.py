"""Published peaks of the chips the benchmark may run on, keyed by the exact
`device_kind` JAX reports. A device that is not in the table is an error,
never a default: a share of a peak computed against the wrong peak is a
wrong number under a right name."""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of HBM
    # at 819 GB/s per chip.
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks on record for device_kind {device_kind!r}; "
            "add it to benchmark/peaks.py with its source") from None


def roofline_share(flops: float, nbytes: float, seconds: float,
                   device_kind: str) -> tuple[float, str]:
    """(percent of the roofline reached, which bound holds): the least time
    the chip could take, the larger of operations over peak FLOP/s and bytes
    over peak bytes/s, over the time it took."""
    p = peaks_for(device_kind)
    t_flops = flops / p["flops_bf16"]
    t_bytes = nbytes / p["hbm_bytes_per_s"]
    bound = "compute" if t_flops >= t_bytes else "memory"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound


def mfu(flops: float, seconds: float, device_kind: str,
        chips: int = 1) -> float:
    """Percent of the chips' peak bf16 FLOP/s that ``flops`` in ``seconds``
    amount to."""
    return 100.0 * flops / (seconds * chips * peaks_for(device_kind)["flops_bf16"])

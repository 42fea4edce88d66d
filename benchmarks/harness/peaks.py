"""The table of peaks, the roofline, and the functions that count a
kernel's required operations and bytes from its shapes (a model step's
count is beside its configuration, ``benchmarks/costs/<name>.py``).
This is the yardstick: later PRs cannot change it, and the same count
holds whatever implements the kernel.

Peaks per CHIP, keyed by the exact ``device_kind`` JAX reports.  Source:
Google Cloud documentation, "TPU v5e" -- 197 TFLOP/s bf16, 819 GB/s HBM
("TPU v5 lite" is what the installed libtpu calls a v5e).  A device that
is not in the table is an error, not a default.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "bytes_per_s": 819e9},
    "TPU v5e": {"flops": 197e12, "bytes_per_s": 819e9},
}


def chip_peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise ValueError(
            f"no peak on record for device_kind {device_kind!r}: add it to "
            f"benchmarks/harness/peaks.py with its source")
    return PEAKS[device_kind]


def roofline(flops: float, nbytes: float, device_kind: str) -> dict:
    """Least seconds the chip could take for ``flops`` operations and
    ``nbytes`` bytes of HBM traffic, and which of the two bounds it."""
    peak = chip_peaks(device_kind)
    t_flops = flops / peak["flops"]
    t_bytes = nbytes / peak["bytes_per_s"]
    return {"min_s": max(t_flops, t_bytes),
            "bound": "compute" if t_flops >= t_bytes else "memory"}


def attention_fwd_cost(batch_heads: int, seq_len: int, head_dim: int,
                       causal: bool, itemsize: int) -> dict:
    """One forward attention call over ``[BH, L, D]``: QK^T and PV
    (2 * L * L * D multiply-adds each, half under a causal mask), and
    the least HBM traffic: q, k, v read once, the output written once,
    plus one float32 log-sum-exp per row."""
    flops = 2 * 2.0 * batch_heads * seq_len * seq_len * head_dim
    if causal:
        flops /= 2
    nbytes = 4.0 * batch_heads * seq_len * head_dim * itemsize \
        + 4.0 * batch_heads * seq_len
    return {"flops": flops, "bytes": nbytes}

"""The one table of device peaks, keyed by ``device_kind`` as JAX reports it.
A device that is not in the table is an error, never a default."""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        # Google Cloud documentation, "TPU v5e": 16 GB of HBM at 819 GB/s
        "hbm_bytes_per_s": 819e9,
        "hbm_source": "Google Cloud documentation, TPU v5e (published)",
        # no published figure: best of benchmark/probe_u32.py on one v5e
        "u32_ops_per_s": 5.989e12,   # 5,988,936,271,174 at (16384, 2048) x 64 rounds
        "u32_source": "measured, PR 25: benchmark/probe_u32.py",
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peaks for device kind {device_kind!r}: add a row to "
            f"benchmark/harness/peaks.py with its source") from None

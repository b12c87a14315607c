"""Published peaks of one chip, keyed by ``device_kind`` as JAX reports it.

Copied from ``bench.py``'s ``_PEAK_BF16`` (rows kept where all three
numbers have a public source), with the memory bandwidth and size beside
the FLOP/s. A device that is not in the table is an error, not a default.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peak:
    bf16_flops_per_s: float
    hbm_bytes_per_s: float
    hbm_bytes: float
    source: str


PEAKS = {
    "TPU v5 lite": Peak(197e12, 819e9, 16e9,
                        'Google Cloud documentation, "TPU v5e"'),
    "TPU v6 lite": Peak(918e12, 1640e9, 32e9,
                        'Google Cloud documentation, "TPU v6e"'),
    "TPU v4": Peak(275e12, 1228e9, 32e9,
                   'Google Cloud documentation, "TPU v4"'),
}


class UnknownDevice(RuntimeError):
    """The device has no row in ``PEAKS``."""


def peak(device_kind: str) -> Peak:
    if device_kind not in PEAKS:
        raise UnknownDevice(
            f"no published peaks known for device kind {device_kind!r} "
            f"(known: {', '.join(sorted(PEAKS))}); add its row to "
            "benchmark/peaks.py with its source")
    return PEAKS[device_kind]

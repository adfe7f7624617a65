"""Published peaks of each chip the benchmark may run on, keyed by the
`device_kind` JAX reports. A device missing here is an error, never a
default: a share of a peak that was guessed is no measurement."""
from __future__ import annotations

from typing import Dict

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 (the only peak a
# metric reads; a metric that needs another adds it with its source)
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12},
}


def peaks(device_kind: str) -> Dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None

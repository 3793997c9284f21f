"""Published peaks of one chip, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s
int8, 16 GB of HBM at 819 GB/s per chip.  A kind that is not in the table is
an error, never a default.
"""

from __future__ import annotations

from typing import Dict, NamedTuple


class Peaks(NamedTuple):
    bf16_flops: float   # dense bf16 FLOP/s
    int8_ops: float     # dense int8 OP/s
    hbm_bw: float       # HBM bytes/s


PEAKS: Dict[str, Peaks] = {
    "TPU v5 lite": Peaks(bf16_flops=197e12, int8_ops=393e12, hbm_bw=819e9),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device_kind {device_kind!r};"
                       f" known: {sorted(PEAKS)}") from None

"""Published peaks of each accelerator, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
per chip 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at 819 GB/s.
A ``device_kind`` that is not in the table is an error, never a default.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "bf16_flops_per_s": 197e12,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peak(kind: str, table: dict = PEAKS) -> dict:
    """The peaks of ``kind``; raises ``KeyError`` for a kind the table does
    not list."""
    if kind not in table:
        raise KeyError(f"no published peaks for device kind {kind!r}; the "
                       f"table lists {sorted(table)}")
    return table[kind]

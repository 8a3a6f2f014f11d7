"""Published peaks of each accelerator, keyed by JAX's ``device_kind``.

A device that is not in the table is an error, never a default."""
from __future__ import annotations

from typing import Dict

#: device_kind -> peaks.  Source: Google Cloud documentation, "TPU v5e"
#: (cloud.google.com/tpu/docs/v5e): 197 TFLOP/s bf16, 393 TOP/s int8,
#: 16 GB HBM at 819 GB/s per chip.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}
PEAKS["TPU v5e"] = PEAKS["TPU v5 lite"]


def peaks(device_kind: str) -> Dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add "
                       f"them to {__name__}.PEAKS with their source") \
            from None


#: bfloat16 MXU passes per float32 product, by the configuration's
#: ``matmul_precision`` (JAX's names): a float32 product costs this many
#: times its FLOPs at the bf16 peak
MATMUL_PASSES: Dict[str, int] = {"bfloat16": 1, "tensorfloat32": 3,
                                 "float32": 6}

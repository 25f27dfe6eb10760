"""What every family's counts share.  The operations and bytes a model's
work needs are its family's ``Shapes`` (``bench/families/<model_type>.py``,
read by metrics as ``ctx.shapes``); here are the bytes of a served dtype
and the roofline that turns counts into a least time.
"""

from __future__ import annotations

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def least_time(flops: float, nbytes: float, peak: dict) -> float:
    """The roofline: the larger of compute time and memory time."""
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])

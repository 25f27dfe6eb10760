"""What every family's plain reference shares: the precision of its
products and the lower precisions that make its control.

Each family's reference (``Reference`` in
``bench/families/<model_type>.py``) runs every product at ``HI``.
``LOW`` maps a control's name to its rounding: with ``quant="int8"``
(or ``"fp8"``) a reference takes every weight product in that 8-bit
type (weights scaled per output channel, activations per row) and
stores keys and values in it (per token and head); round to nearest.
That is the precision below the bfloat16 that the configurations state.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def fake_int8(x, axis):
    """Round ``x`` to int8 levels with one symmetric scale per slice
    along ``axis`` (the reduction axis of the product it feeds)."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def fake_fp8(x, axis):
    """Round ``x`` to float8_e4m3fn with one scale per slice along
    ``axis`` that puts the slice's largest magnitude at 448."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


LOW = {"int8": fake_int8, "fp8": fake_fp8}

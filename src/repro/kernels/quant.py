"""Symmetric int8 quantization helpers for the two decode bandwidth terms.

GRIM's thesis is that the compressed format and the execution scheme must
be co-designed; this module quantizes exactly the layouts the Pallas
kernels already stream, so the scales ride along with the data they
dequantize and no new gather is introduced:

* **KV rows** — one fp32 scale per cache row per kv head (axis ``-1``
  absmax over ``head_dim``). The paged pools keep the scales in sibling
  ``(n_pages, Hkv, page_size)`` pools that share the K/V page index map,
  so CoW page copies, truncation and DMA elision all apply to the scales
  for free.
* **BCR block values** — one fp32 scale per kept ``(r_keep, c_keep)``
  block tile (absmax over the tile), stored on the plan next to the flat
  take/scatter vectors and folded into the spmm epilogue.

Quantization is symmetric round-to-nearest onto ``[-127, 127]``: with
``s = absmax / 127`` the round-trip error per element is bounded by
``s / 2 = absmax / 254`` (~0.4% of the row/tile absmax), which the tests
assert.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

INT8_MAX = 127.0
# floor for the scale so all-zero rows/tiles quantize to zeros instead of
# dividing by zero (any positive tiny works: codes are 0 either way)
EPS = 1e-12


def quantize_rows(x: jax.Array, scale_dtype=jnp.float32):
    """Quantize over the LAST axis: returns ``(codes int8, scale)`` with
    ``scale.shape == x.shape[:-1]`` and ``x ≈ codes * scale[..., None]``."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.maximum(amax / INT8_MAX, EPS)
    codes = jnp.clip(jnp.round(xf / scale[..., None]), -INT8_MAX, INT8_MAX)
    return codes.astype(jnp.int8), scale.astype(scale_dtype)


def dequantize_rows(codes: jax.Array, scale: jax.Array,
                    dtype=jnp.float32) -> jax.Array:
    """Inverse of :func:`quantize_rows` (up to rounding)."""
    return (codes.astype(jnp.float32)
            * scale.astype(jnp.float32)[..., None]).astype(dtype)


def quantize_blocks(vals: jax.Array):
    """Per-block quantization of packed BCR values.

    ``vals`` is ``(..., nb_r, nb_c, r_keep, c_keep)`` (leading axes for
    stacked layers / fused groups); the scale is the absmax over the
    trailing ``(r_keep, c_keep)`` tile: returns ``(codes int8, scales)``
    with ``scales.shape == vals.shape[:-2]``.
    """
    vf = vals.astype(jnp.float32)
    amax = jnp.max(jnp.abs(vf), axis=(-2, -1))
    scale = jnp.maximum(amax / INT8_MAX, EPS)
    codes = jnp.clip(jnp.round(vf / scale[..., None, None]),
                     -INT8_MAX, INT8_MAX)
    return codes.astype(jnp.int8), scale.astype(jnp.float32)


def dequantize_blocks(codes: jax.Array, scales: jax.Array,
                      dtype=jnp.float32) -> jax.Array:
    """Inverse of :func:`quantize_blocks` (up to rounding)."""
    return (codes.astype(jnp.float32)
            * scales.astype(jnp.float32)[..., None, None]).astype(dtype)

"""Seeded weights of a BCR-pruned dense decoder, made on the device.

The one definition of a cell's weights: the harness hands them to the
program's packer, and the plain reference regenerates them layer by layer
from the same seed. Every projection is dense in shape and balanced-BCR in
support (GRIM's block-column-row pruning): in each ``block × block`` tile
``keep_rows`` whole rows and ``keep_cols`` whole columns survive, chosen
uniformly at random, so the packer's energy-ranked selection keeps exactly
the nonzero rows and columns. Values are normal, scaled by the kept fan-in
so activations keep unit scale through depth.

Layout (``(out, in)`` as in ``y = x @ w.T``): ``wq (Hq*Dh, D)``, ``wk``,
``wv (Hkv*Dh, D)``, ``wo (D, Hq*Dh)``, ``wg``, ``wi (F, D)``, ``wd (D, F)``;
``bq``, ``bk``, ``bv`` biases when ``qkv_bias``; ``n1``, ``n2`` RMSNorm
scales. Head: ``embed (V, D)``, ``final_norm (D,)``, ``lm_head (V, D)``.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

LAYER_NAMES = ("wq", "wk", "wv", "wo", "wg", "wi", "wd", "bq", "bk", "bv",
               "n1", "n2")


def root_key(seed: int) -> jax.Array:
    """A key from any whole-number seed, 64 bits and beyond."""
    seed = int(seed)
    k = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    k = jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(k, (seed >> 64) & 0xFFFFFFFF)


def kept(m: Dict[str, Any]):
    """(rows, cols) kept per block: balanced BCR at ``keep``, split evenly
    between the two axes (block 128, keep 0.25 -> 64 x 64)."""
    b = int(m["bcr_block"])
    side = b * float(m["bcr_keep"]) ** 0.5
    if abs(side - round(side)) > 1e-9:
        raise ValueError(f"keep {m['bcr_keep']} gives no whole kept side "
                         f"in a {b}-block")
    return int(round(side)), int(round(side))


def bcr_dense(key: jax.Array, n: int, k: int, m: Dict[str, Any]
              ) -> jax.Array:
    """A dense ``(n, k)`` fp32 matrix with balanced-BCR support."""
    b = int(m["bcr_block"])
    r_keep, c_keep = kept(m)
    kw, kr, kc = jax.random.split(key, 3)
    nb_r, nb_c = n // b, k // b
    ur = jax.random.uniform(kr, (nb_r, nb_c, b))
    uc = jax.random.uniform(kc, (nb_r, nb_c, b))
    rm = ur <= jnp.sort(ur, axis=-1)[..., r_keep - 1:r_keep]
    cm = uc <= jnp.sort(uc, axis=-1)[..., c_keep - 1:c_keep]
    mask = (rm[..., :, None] & cm[..., None, :])          # (nb_r, nb_c, b, b)
    mask = mask.transpose(0, 2, 1, 3).reshape(n, k)
    # kept fan-in per output row: c_keep of every b columns, in the blocks
    # where the row survives (half of them at keep 0.25)
    fan_in = k * (c_keep / b) * (r_keep / b)
    w = jax.random.normal(kw, (n, k), jnp.float32) * fan_in ** -0.5
    return jnp.where(mask, w, 0.0)


def layer(root: jax.Array, idx, m: Dict[str, Any]) -> Dict[str, jax.Array]:
    """Layer ``idx`` (0-based; traceable) of the model ``m``."""
    d, f = m["hidden_size"], m["intermediate_size"]
    hq = m["num_attention_heads"] * m["head_dim"]
    hkv = m["num_key_value_heads"] * m["head_dim"]
    ks = dict(zip(LAYER_NAMES, jax.random.split(
        jax.random.fold_in(root, idx + 1), len(LAYER_NAMES))))
    out = {
        "wq": bcr_dense(ks["wq"], hq, d, m),
        "wk": bcr_dense(ks["wk"], hkv, d, m),
        "wv": bcr_dense(ks["wv"], hkv, d, m),
        "wo": bcr_dense(ks["wo"], d, hq, m),
        "wg": bcr_dense(ks["wg"], f, d, m),
        "wi": bcr_dense(ks["wi"], f, d, m),
        "wd": bcr_dense(ks["wd"], d, f, m),
        "n1": 1.0 + 0.1 * jax.random.normal(ks["n1"], (d,), jnp.float32),
        "n2": 1.0 + 0.1 * jax.random.normal(ks["n2"], (d,), jnp.float32),
    }
    if m["qkv_bias"]:
        out["bq"] = 0.1 * jax.random.normal(ks["bq"], (hq,), jnp.float32)
        out["bk"] = 0.1 * jax.random.normal(ks["bk"], (hkv,), jnp.float32)
        out["bv"] = 0.1 * jax.random.normal(ks["bv"], (hkv,), jnp.float32)
    return out


def head(root: jax.Array, m: Dict[str, Any]) -> Dict[str, jax.Array]:
    d, v = m["hidden_size"], m["vocab_size"]
    ke, kn, kh = jax.random.split(jax.random.fold_in(root, 0), 3)
    return {
        "embed": jax.random.normal(ke, (v, d), jnp.float32) * d ** -0.5,
        "final_norm": 1.0 + 0.1 * jax.random.normal(kn, (d,), jnp.float32),
        "lm_head": bcr_dense(kh, v, d, m),
    }

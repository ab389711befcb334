"""Plain float32 reference of a pre-norm dense decoder (Llama/Qwen2/Mistral
layer equations): RMSNorm, rotate-half RoPE, grouped-query causal softmax
attention with optional Q/K/V bias, SwiGLU MLP, untied LM head.

Runs layer by layer over a padded batch of whole sequences, regenerating
each layer's weights from the seed (``seeded.py``), at
``jax.default_matmul_precision("highest")``. Departures from a published
checkpoint: weights are random and BCR-pruned (zeros where the program
stores nothing); nothing else.
"""

from __future__ import annotations

from typing import Any, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import seeded


def rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def rope(x, positions, theta):
    """x: (T, H, Dh); rotate-half convention."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[:, None].astype(jnp.float32) * freqs        # (T, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer_one(w, x, m):
    """One layer over one sequence x: (T, D)."""
    t = x.shape[0]
    hq, hkv, dh = (m["num_attention_heads"], m["num_key_value_heads"],
                   m["head_dim"])
    eps, theta = m["rms_norm_eps"], m["rope_theta"]
    pos = jnp.arange(t)
    h = rmsnorm(x, w["n1"], eps)
    q, k, v = h @ w["wq"].T, h @ w["wk"].T, h @ w["wv"].T
    if m["qkv_bias"]:
        q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
    q = rope(q.reshape(t, hq, dh), pos, theta)
    k = rope(k.reshape(t, hkv, dh), pos, theta)
    v = v.reshape(t, hkv, dh)
    g = hq // hkv
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) * dh ** -0.5
    causal = pos[:, None] >= pos[None, :]
    s = jnp.where(causal[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v).reshape(t, hq * dh)
    x = x + o @ w["wo"].T
    h2 = rmsnorm(x, w["n2"], eps)
    y = (jax.nn.silu(h2 @ w["wg"].T) * (h2 @ w["wi"].T)) @ w["wd"].T
    return x + y


def _make_fns(m: Dict[str, Any]):
    mk = {k: m[k] for k in ("hidden_size", "intermediate_size",
                            "num_attention_heads", "num_key_value_heads",
                            "head_dim", "qkv_bias", "rms_norm_eps",
                            "rope_theta", "bcr_block", "bcr_keep",
                            "vocab_size")}

    @jax.jit
    def embed_fn(root, tokens):
        return seeded.head(root, mk)["embed"][tokens]

    @jax.jit
    def layer_fn(root, idx, xs):
        w = seeded.layer(root, idx, mk)
        return jax.lax.map(lambda x: _layer_one(w, x, mk), xs)

    @jax.jit
    def gaps_fn(root, hs, served):
        """Widest gap below the best logit of each served token, per row:
        hs (N, D) hidden rows that predict ``served`` (N,)."""
        hw = seeded.head(root, mk)
        logits = rmsnorm(hs, hw["final_norm"], mk["rms_norm_eps"]) \
            @ hw["lm_head"].T
        best = jnp.max(logits, axis=-1)
        got = jnp.take_along_axis(logits, served[:, None], axis=1)[:, 0]
        return best - got

    return embed_fn, layer_fn, gaps_fn


def served_gaps(m: Dict[str, Any], seed: int,
                seqs: Sequence[Sequence[int]], served: Sequence[Sequence[int]],
                pad_to: int) -> List[np.ndarray]:
    """For each request (prompt ``seqs[r]``, served tokens ``served[r]``)
    the gap of every served token below the reference's best logit at its
    position. Sequences are right-padded to ``pad_to`` (causal, so pads
    change nothing before them)."""
    embed_fn, layer_fn, gaps_fn = _make_fns(m)
    root = seeded.root_key(seed)
    toks = np.zeros((len(seqs), pad_to), np.int32)
    for r, (p, s) in enumerate(zip(seqs, served)):
        full = list(p) + list(s[:-1])
        toks[r, :len(full)] = full
    with jax.default_matmul_precision("highest"):
        xs = embed_fn(root, jnp.asarray(toks))
        for idx in range(m["num_hidden_layers"]):
            xs = layer_fn(root, jnp.int32(idx), xs)
        # the hidden row at position P-1+j predicts served token j
        idx_r = np.concatenate([np.full(len(s), r)
                                for r, s in enumerate(served)])
        idx_t = np.concatenate([np.arange(len(p) - 1, len(p) - 1 + len(s))
                                for p, s in zip(seqs, served)])
        want = np.concatenate([np.asarray(s) for s in served])
        hs = xs[jnp.asarray(idx_r), jnp.asarray(idx_t)]
        del xs
        g = np.asarray(gaps_fn(root, hs, jnp.asarray(want, jnp.int32)))
    return [g[idx_r == r] for r in range(len(seqs))]

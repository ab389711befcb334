"""Pre-norm dense decoder (Llama/Qwen2/Mistral): GQA attention and a SwiGLU
FFN in every layer, all layers stacked, untied LM head. Its plain
reference is ``reference/dense_decoder.py``."""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

import jax

from reference import seeded
from work import bcr_spmm, paged_attention

#: published config keys -> the program's ModelConfig fields
KEYS = {"num_hidden_layers": "num_layers", "hidden_size": "d_model",
        "num_attention_heads": "num_heads",
        "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
        "intermediate_size": "d_ff", "vocab_size": "vocab_size",
        "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
        "qkv_bias": "qkv_bias"}


def head(root: jax.Array, m: Dict[str, Any]) -> Dict[str, Any]:
    h = seeded.head(root, m)
    return {"embed": {"table": h["embed"]},
            "final_norm": {"scale": h["final_norm"]},
            "lm_head": {"w": h["lm_head"]}}


def layer(w: Dict[str, jax.Array], qkv_bias: bool) -> Dict[str, Any]:
    """Seeded layer weights (``seeded.layer``) in the program's layout."""
    def lin(name, bias=None):
        out = {"w": w[name]}
        if bias is not None:
            out["b"] = w[bias]
        return out
    b = qkv_bias
    return {
        "norm1": {"scale": w["n1"]},
        "mixer": {"wq": lin("wq", "bq" if b else None),
                  "wk": lin("wk", "bk" if b else None),
                  "wv": lin("wv", "bv" if b else None),
                  "wo": lin("wo")},
        "norm2": {"scale": w["n2"]},
        "ffn": {"wg": lin("wg"), "wi": lin("wi"), "wo": lin("wd")},
    }


def prefix(root: jax.Array, m: Dict[str, Any]) -> List[Dict[str, Any]]:
    return []


def stack(root: jax.Array, layers: jax.Array, m: Dict[str, Any]
          ) -> List[Dict[str, Any]]:
    return [jax.vmap(lambda i: layer(seeded.layer(root, i, m),
                                     m["qkv_bias"]))(layers)]


def matrices(m: Dict[str, Any]) -> List[Tuple[int, int]]:
    """(N, K) of every BCR projection of the model: each layer's Q, K, V,
    O, gate, up and down, then the LM head."""
    d, f = m["hidden_size"], m["intermediate_size"]
    hq = m["num_attention_heads"] * m["head_dim"]
    hkv = m["num_key_value_heads"] * m["head_dim"]
    one = [(hq, d), (hkv, d), (hkv, d), (d, hq), (f, d), (f, d), (d, f)]
    return one * m["num_hidden_layers"] + [(m["vocab_size"], d)]


def bcr_spmm_work(m: Dict[str, Any], args: Dict[str, Any]
                  ) -> Tuple[float, float]:
    return bcr_spmm.work(matrices(m), float(m["bcr_keep"]), args["rows"])


def paged_attention_work(m: Dict[str, Any], args: Dict[str, Any]
                         ) -> Tuple[float, float]:
    return paged_attention.work(m, m["num_hidden_layers"], args["rows"],
                                args["ctx_tokens"])


def decode_step_work(m: Dict[str, Any], args: Dict[str, Any]
                     ) -> Tuple[float, float]:
    """The whole decode step: the BCR projections and LM head, decode
    attention, and the embedding rows read (norms and elementwise work are
    left out: they are a rounding error of the bytes)."""
    f1, b1 = bcr_spmm_work(m, args)
    f2, b2 = paged_attention_work(m, args)
    emb = args["rows"] * m["hidden_size"] * bcr_spmm.BF16
    return f1 + f2, b1 + b2 + emb


WORK = {"bcr_spmm": bcr_spmm_work, "paged_attention": paged_attention_work,
        "decode_step": decode_step_work}

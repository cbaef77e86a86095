"""Plain float32 forward of the dense GQA decoder block, from its config.

Straight ``jax.numpy`` with every matrix product at ``Precision.HIGHEST``
(``common.py``): no kernels, no cache, no paging, no batching. It reads the
architecture from the configuration file (Hugging Face keys) and the weights
from the pytree the benchmark made from the seed (``bench/model.py``, laid
out by ``bench/blocks/gqa.py``), never from the program.

The block, as the configuration states it:

  x = embed[tokens]
  per layer:  h = rms(x) * ln1;  q, k, v = h Wq (+bq), h Wk (+bk), h Wv (+bv)
              rotary (half-split) on q and k at positions 0..L-1
              causal softmax(q k^T / sqrt(hd)) v over the group's kv head
              x += attn Wo;  h = rms(x) * ln2
              x += silu(h Wg) * (h Wu) Wd     (hidden_act "silu")
                or relu(h Wi)^2 Wo            (hidden_act "relu2")
  logits = (rms(x) * final) head,  head = embed^T when tied

``quant`` names the control's lower precision (``common.mm``).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from reference.common import mm as _mm, rms as _rms, rope as _rope


@dataclasses.dataclass(frozen=True)
class Dims:
    d: int
    n_heads: int
    n_kv: int
    hd: int
    ff: int
    vocab: int
    layers: int
    eps: float
    theta: float
    act: str
    bias: bool
    tied: bool

    @classmethod
    def of(cls, conf: dict) -> "Dims":
        d, H = conf["hidden_size"], conf["num_attention_heads"]
        return cls(d=d, n_heads=H, n_kv=conf["num_key_value_heads"],
                   hd=conf.get("head_dim") or d // H,
                   ff=conf["intermediate_size"], vocab=conf["vocab_size"],
                   layers=conf["num_hidden_layers"],
                   eps=float(conf.get("rms_norm_eps", conf.get("norm_eps"))),
                   theta=float(conf["rope_theta"]), act=conf["hidden_act"],
                   bias=bool(conf.get("attention_bias", False)),
                   tied=bool(conf["tie_word_embeddings"]))


def hidden(params, tokens, dims: Dims, quant: Optional[str] = None):
    """Final-normed hidden states [L, d] of one sequence ``tokens`` [L]."""
    mm = _mm(quant)
    f32 = lambda a: a.astype(jnp.float32)
    L = tokens.shape[0]
    H, K, hd = dims.n_heads, dims.n_kv, dims.hd
    x = f32(params["embed"])[tokens]
    causal = jnp.tril(jnp.ones((L, L), bool))
    layer0 = params["groups"][0][0]

    def block(x, p):
        p = jax.tree.map(f32, p)
        a, m = p["mixer"], p["ffn"]
        h = _rms(x, p["ln1"]["scale"], dims.eps)
        q, k, v = mm(h, a["wq"]), mm(h, a["wk"]), mm(h, a["wv"])
        if dims.bias:
            q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
        q = _rope(q.reshape(L, H, hd), dims.theta)
        k = _rope(k.reshape(L, K, hd), dims.theta)
        v = v.reshape(L, K, hd)
        G = H // K
        q = q.reshape(L, K, G, hd).transpose(1, 2, 0, 3)        # [K, G, L, hd]
        kt = k.transpose(1, 2, 0)[:, None]                       # [K, 1, hd, L]
        s = mm(q, kt) / jnp.sqrt(jnp.float32(hd))                # [K, G, L, L]
        s = jnp.where(causal, s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        o = mm(w, v.transpose(1, 0, 2)[:, None])                 # [K, G, L, hd]
        o = o.transpose(2, 0, 1, 3).reshape(L, H * hd)
        x = x + mm(o, a["wo"])
        h = _rms(x, p["ln2"]["scale"], dims.eps)
        if dims.act == "silu":
            y = mm(jax.nn.silu(mm(h, m["w_gate"])) * mm(h, m["w_up"]),
                   m["w_down"])
        elif dims.act == "relu2":
            y = mm(jnp.square(jax.nn.relu(mm(h, m["w_in"]))), m["w_out"])
        else:
            raise ValueError(f"hidden_act {dims.act!r} has no reference")
        return x + y, None

    x, _ = jax.lax.scan(block, x, layer0)
    return _rms(x, f32(params["final_norm"]["scale"]), dims.eps)


def head(params, dims: Dims):
    """The output projection [d, vocab] in float32."""
    if dims.tied:
        return params["embed"].astype(jnp.float32).T
    return params["lm_head"].astype(jnp.float32)

"""Operations and bytes that the algorithm needs for the tokens served.

The block the configuration names gives the matrix products of its layers
(``bench/blocks/<block>.py``, ``layer_flops``); this module adds the
attention over the context, the output head and the K/V bytes, which follow
from the head counts alone.

The count is of the work the tokens need, whatever computes it: it does not
follow the kernels' page walks, padding or chunking, so a faster
implementation shows as a higher share of the roofline and no implementation
can read above 100%. A matrix product [m, k] x [k, n] is 2mkn operations
(the convention of the program's ``launch/accounting.py``, copied here).

  * A decode token at context c (its prompt plus the tokens before it, itself
    included) attends over c positions in every layer.
  * A prompt position p computed by a prefill chunk attends causally over
    the p + 1 positions up to it; positions served from the prefix cache are
    not computed and not counted.
  * The output head is counted once per emitted token.
  * Attention bytes are the live K/V the token needs (bf16), read once per kv
    head, plus q and the output (float32, as the kernels take and give them).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Iterable, Sequence, Tuple

KV_BYTES = 2        # bf16 pages
QO_BYTES = 4        # float32 q in, float32 out of the attention kernels


@dataclasses.dataclass(frozen=True)
class Shape:
    d: int
    n_heads: int
    n_kv: int
    hd: int
    vocab: int
    layers: int
    matmul_flops: int   # every layer's matrix products, for one token

    @classmethod
    def of(cls, conf: Dict, layer_flops: Sequence[int]) -> "Shape":
        """``layer_flops``: the block's matrix-product FLOPs one token needs
        in each layer (``bench/blocks/<block>.py``), every layer attending."""
        d, H = conf["hidden_size"], conf["num_attention_heads"]
        return cls(d=d, n_heads=H, n_kv=conf["num_key_value_heads"],
                   hd=conf.get("head_dim") or d // H,
                   vocab=conf["vocab_size"], layers=len(layer_flops),
                   matmul_flops=sum(layer_flops))


def head_flops(s: Shape) -> int:
    return 2 * s.d * s.vocab


def attn_flops(s: Shape, context: int) -> int:
    """q k^T and p v of one query token over ``context`` positions, all
    layers."""
    return s.layers * 4 * s.n_heads * s.hd * context


def decode_attn_bytes(s: Shape, context: int) -> int:
    """Live K/V of ``context`` positions plus q and out, all layers."""
    kv = 2 * s.n_kv * s.hd * context * KV_BYTES
    qo = 2 * s.n_heads * s.hd * QO_BYTES
    return s.layers * (kv + qo)


def prefill_attn(s: Shape, start: int, size: int) -> Tuple[int, int]:
    """(flops, bytes) of the attention of one prefill chunk covering prompt
    positions [start, start + size): causal over each position's prefix; the
    chunk reads the live K/V of its start + size positions once."""
    # sum over p in [start, start + size) of (p + 1)
    pairs = size * start + size * (size + 1) // 2
    flops = s.layers * 4 * s.n_heads * s.hd * pairs
    kv = 2 * s.n_kv * s.hd * (start + size) * KV_BYTES
    qo = 2 * size * s.n_heads * s.hd * QO_BYTES
    return flops, s.layers * (kv + qo)


@dataclasses.dataclass
class Work:
    """Work counted over a window."""
    model_flops: float = 0.0
    prefill_model_flops: float = 0.0   # prompt positions computed, no head
    decode_attn_flops: float = 0.0
    decode_attn_bytes: float = 0.0
    prefill_attn_flops: float = 0.0
    prefill_attn_bytes: float = 0.0
    decode_tokens: int = 0
    prefill_tokens: int = 0
    emitted_tokens: int = 0


def count(s: Shape, decode_contexts: Iterable[int],
          chunks: Iterable[Tuple[int, int]], emitted: int) -> Work:
    """Work of the decode tokens (their contexts), the prefill chunks
    ((start, size) each) and the ``emitted`` tokens' output head."""
    w = Work()
    per_tok = s.matmul_flops
    for c in decode_contexts:
        w.decode_tokens += 1
        w.decode_attn_flops += attn_flops(s, c)
        w.decode_attn_bytes += decode_attn_bytes(s, c)
    for start, size in chunks:
        f, b = prefill_attn(s, start, size)
        w.prefill_tokens += size
        w.prefill_attn_flops += f
        w.prefill_attn_bytes += b
    w.emitted_tokens = emitted
    w.prefill_model_flops = w.prefill_tokens * per_tok + w.prefill_attn_flops
    w.model_flops = (w.prefill_model_flops + w.decode_tokens * per_tok
                     + w.decode_attn_flops + emitted * head_flops(s))
    return w


def least_time(flops: float, nbytes: float, peak: Dict) -> Tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    t_f = flops / peak["bf16_flops_per_s"]
    t_b = nbytes / peak["hbm_bytes_per_s"]
    return (t_f, "compute") if t_f >= t_b else (t_b, "memory")

"""The comparison that decides ``correct`` for a served model.

For each sampled request, its prompt and every token the engine served are
teacher-forced through the float32 reference of the configuration's block
(``reference/<block>.py``: ``Dims``, ``hidden``, ``head``) in one sequence.
At each position that produced a served token, the gap is how far the
served token's reference logit lies below the reference's best logit there,
in standard deviations of that reference logit row (greedy serving picks the
argmax, so a sound path reads near 0 and a wrong one far above). The number
compared is the widest such gap over all the sampled tokens.

The control (``served_gaps`` with ``quant``) runs the same sequences through
the reference computed in a lower precision and reads, at the same
positions, the float32 gap of the token that the lower precision puts first.

Every sequence is padded to one length, so each cell compiles this once.
Rows are reduced to their gap inside the program in blocks, so no logits
array of [length, vocab] is ever held whole.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from reference import common

ROW_BLOCK = 256


@functools.lru_cache(maxsize=8)
def _gap_fn(ref, dims, length: int, quant: Optional[str]):
    n_blk = -(-length // ROW_BLOCK)

    def gaps(params, tokens):
        """tokens [length]; returns per row r the gap of tokens[r+1] (or, with
        ``quant``, of the lower-precision argmax) below the f32 best."""
        h = ref.hidden(params, tokens, dims)
        if quant is not None:
            hq = ref.hidden(params, tokens, dims, quant)
        w = ref.head(params, dims)
        pad = n_blk * ROW_BLOCK - length
        target = jnp.pad(tokens[1:], (0, pad + 1))
        hp = jnp.pad(h, ((0, pad), (0, 0)))
        hq = jnp.pad(hq, ((0, pad), (0, 0))) if quant is not None else hp

        def block(i):
            rows = jax.lax.dynamic_slice_in_dim(hp, i * ROW_BLOCK, ROW_BLOCK)
            lg = jnp.matmul(rows, w, precision=common.HIGHEST)
            tgt = jax.lax.dynamic_slice_in_dim(target, i * ROW_BLOCK,
                                               ROW_BLOCK)
            if quant is not None:
                rq = jax.lax.dynamic_slice_in_dim(hq, i * ROW_BLOCK,
                                                  ROW_BLOCK)
                tgt = jnp.argmax(common.mm(quant)(rq, w), axis=-1)
            chosen = jnp.take_along_axis(lg, tgt[:, None], 1)[:, 0]
            return (jnp.max(lg, -1) - chosen) / jnp.std(lg, -1)

        return jax.lax.map(block, jnp.arange(n_blk)).reshape(-1)[:length]

    return jax.jit(gaps)


def served_gaps(params, ref, dims, length: int, samples: Sequence[Dict],
                quant: Optional[str] = None) -> List[np.ndarray]:
    """``ref``: the block's reference module, ``dims`` its ``Dims.of`` the
    configuration. ``samples``: dicts with ``prompt`` and ``served`` token
    lists. Returns, per sample, the gap at each served token (float32
    reference), or with ``quant`` the control's gap at the same positions."""
    fn = _gap_fn(ref, dims, length, quant)
    out = []
    for s in samples:
        prompt, served = list(s["prompt"]), list(s["served"])
        seq = np.zeros(length, np.int32)
        seq[:len(prompt) + len(served)] = prompt + served
        g = np.asarray(fn(params, jnp.asarray(seq)))
        lo = len(prompt) - 1
        out.append(g[lo:lo + len(served)])
    return out

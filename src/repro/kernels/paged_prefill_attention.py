"""Paged flash-*prefill* Pallas kernel: a chunk of queries vs a paged prefix.

The serving-layer analogue of HEROv2's tiled offload (§3): instead of one
monolithic prefill whose latency stalls every decoding stream, the prompt is
cut into bounded token chunks and each chunk's queries attend against the
*paged* KV prefix — the same physical page pool and page-table indirection the
flash-decode kernel walks (kernels/paged_decode_attention.py), but with a
block of C queries and a causal mask that is exact **across chunk
boundaries**: the query at global position ``start + i`` sees keys at
positions ``<= start + i``, whether those keys were written by an earlier
chunk (a different dispatch) or by this one.

Kernel structure: grid (K, max_pages) with kv pages innermost and (m, l,
acc) online-softmax scratch carried across them; the page-table walk
happens in the BlockSpec index_map via scalar prefetch.
Two scalars ride along in the prefetch: the page table row and ``start`` (the
chunk's global query offset) — the causal frontier is a *runtime* value, so
one compiled kernel serves every chunk of a given size.

Single-sequence by design: a chunk belongs to one request (the engine
dispatches one chunk per prefilling request per iteration), so B=1 is the
natural shape and the grid stays (K, pages), not (B·K, pages).

Validated in interpret mode against the dense oracle over chunk sizes 1/3/
budget and page-boundary-crossing starts (tests/test_kernels.py).

Tensor parallelism: like the decode kernel, the grid's kv-head dimension
(K) carries no cross-head computation, so serve/executor.py shard_maps the
chunk step with the page pools sliced along kv heads and the chunk queries
sliced to the matching head block — per-shard outputs concatenate
bit-identically to the unsharded call (page table and ``start`` replicated).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.paged_decode_attention import gather_pages, head_scale

NEG = -1e30


def paged_flash_prefill(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                        page_table: jax.Array, start: jax.Array,
                        k_scale=None, v_scale=None, *,
                        interpret: bool) -> jax.Array:
    """Chunk attention over a paged KV cache with cross-chunk causal masking.

    q:          [C, H, hd] — chunk queries at global positions
                ``start .. start+C-1``
    k_pages:    [P, K, pt, hd] physical page pool (the chunk's own K/V must
                already be scattered in — see serve.paged_step.scatter_chunk)
    v_pages:    [P, K, pt, hd]
    page_table: [max_pages] int32 page ids of this sequence, -1 = unmapped
    start:      scalar int32 — KV rows that precede this chunk
    k_scale:    optional [P, K] f32 per-(page, kv-head) dequant scales for an
                int8 pool (serve/kvquant.py): the page block dequantizes in
                VMEM (int8 rows × scale → f32) before the f32 accumulation;
                the scale BlockSpec walks the same prefetched page table.
    v_scale:    optional [P, K] f32 (must accompany ``k_scale``)
    interpret:  run the Pallas body in the interpreter (CPU) instead of
                compiling it with Mosaic (TPU).
    Returns [C, H, hd].
    """
    C, H, hd = q.shape
    P, K, pt, _ = k_pages.shape
    G = H // K
    max_pages = page_table.shape[0]
    scale = 1.0 / math.sqrt(hd)
    quant = k_scale is not None
    if quant != (v_scale is not None):
        raise ValueError("paged_flash_prefill: k_scale and v_scale must be "
                         "given together")

    # head h = k·G + g, matching ref.decode_attention's grouping
    qr = jnp.transpose(q.reshape(C, K, G, hd), (1, 0, 2, 3))   # [K, C, G, hd]
    table = jnp.maximum(page_table.astype(jnp.int32), 0)
    meta = jnp.reshape(start.astype(jnp.int32), (1,))

    def kernel(tbl_ref, meta_ref, q_ref, k_ref, v_ref, *rest):
        if quant:
            ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
        else:
            o_ref, m_ref, l_ref, acc_ref = rest
        kk = pl.program_id(0)
        j = pl.program_id(1)

        @pl.when(j == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        kv_len = meta_ref[0] + C                     # keys visible to row C-1

        @pl.when(j * pt < kv_len)
        def _page():
            qb = q_ref[0].astype(jnp.float32).reshape(C * G, hd)
            kb = k_ref[0, 0].astype(jnp.float32)     # [pt, hd]
            vb = v_ref[0, 0].astype(jnp.float32)
            if quant:
                # dequantize in VMEM: int8 page block × per-(page, head)
                # scale → f32, feeding the same f32 accumulation below
                kb = kb * head_scale(ks_ref, kk, pt)
                vb = vb * head_scale(vs_ref, kk, pt)
            s = jnp.dot(qb, kb.T, preferred_element_type=jnp.float32) * scale
            # cross-chunk causal frontier: row r is query c = r // G at
            # global position start + c; key col is global position j·pt + col
            qpos = meta_ref[0] + \
                jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // G
            kpos = j * pt + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(kpos <= qpos, s, NEG)
            m_prev = m_ref[...]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[:, None])
            corr = jnp.exp(m_prev - m_new)
            l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1)
            acc_ref[...] = acc_ref[...] * corr[:, None] + \
                jnp.dot(p, vb, preferred_element_type=jnp.float32)
            m_ref[...] = m_new

        @pl.when(j == pl.num_programs(1) - 1)
        def _fin():
            out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[:, None]
            o_ref[0] = out.reshape(C, G, hd).astype(o_ref.dtype)

    from jax.experimental.pallas import tpu as pltpu

    in_specs = [
        pl.BlockSpec((1, C, G, hd), lambda kk, j, tbl, meta: (kk, 0, 0, 0)),
        pl.BlockSpec((1, 1, pt, hd),
                     lambda kk, j, tbl, meta: (tbl[j], kk, 0, 0)),
        pl.BlockSpec((1, 1, pt, hd),
                     lambda kk, j, tbl, meta: (tbl[j], kk, 0, 0)),
    ]
    inputs = [table, meta, qr, k_pages, v_pages]
    if quant:
        # scale rows walk the same prefetched table as their pages, in a
        # [P, 1, K] view (see head_scale)
        in_specs += [pl.BlockSpec((1, 1, K), lambda kk, j, tbl, meta:
                                  (tbl[j], 0, 0))] * 2
        inputs += [k_scale.reshape(P, 1, K), v_scale.reshape(P, 1, K)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,          # page_table, meta (start)
        grid=(K, max_pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, C, G, hd),
                               lambda kk, j, tbl, meta: (kk, 0, 0, 0)),
        scratch_shapes=[pltpu.VMEM((C * G,), jnp.float32),
                        pltpu.VMEM((C * G,), jnp.float32),
                        pltpu.VMEM((C * G, hd), jnp.float32)],
    )

    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((K, C, G, hd), q.dtype),
        interpret=interpret,
        name="paged_prefill_attention",
    )(*inputs)
    return jnp.transpose(out, (1, 0, 2, 3)).reshape(C, H, hd)


def paged_prefill_attention_ref(q, k_pages, v_pages, page_table, start,
                                k_scale=None, v_scale=None):
    """Oracle: gather the pages dense (dequantizing first when scales are
    given), masked softmax with the same cross-chunk causal frontier (test
    oracle + debugging)."""
    if k_scale is not None:
        from repro.kernels.paged_decode_attention import dequant_pages
        k_pages = dequant_pages(k_pages, k_scale)
        v_pages = dequant_pages(v_pages, v_scale)
    C, H, hd = q.shape
    K = k_pages.shape[1]
    G = H // K
    k_dense = gather_pages(k_pages, page_table[None])[0]       # [K, S, hd]
    v_dense = gather_pages(v_pages, page_table[None])[0]
    S = k_dense.shape[1]
    qg = q.reshape(C, K, G, hd).astype(jnp.float32)
    logits = jnp.einsum("ckgd,ksd->kgcs", qg, k_dense.astype(jnp.float32))
    logits = logits / math.sqrt(hd)
    qpos = start + jnp.arange(C)[:, None]                      # [C, 1]
    kpos = jnp.arange(S)[None, :]                              # [1, S]
    mask = kpos <= qpos                                        # [C, S]
    logits = jnp.where(mask[None, None], logits, NEG)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("kgcs,ksd->ckgd", p, v_dense.astype(jnp.float32))
    return out.reshape(C, H, hd).astype(q.dtype)

"""Paged flash-decode Pallas kernel: page-table KV gather + online softmax.

HEROv2's SVM insight (§2.2) applied to the serving hot loop: the KV cache is
a pool of fixed-size *physical pages* ([n_pages, K, page_tokens, hd]) and each
sequence owns an ordered *page list*. The device-side page table (int32 rows,
per the addrspace promotion analysis — page *ids* stay native 32-bit even when
page *byte offsets* exceed 2³¹) translates logical token position → physical
page, exactly like the paper's IOMMU translates accelerator-virtual → host-
physical addresses.

Kernel structure mirrors kernels/decode_attention.flash_decode: grid
(B·K, max_pages) with kv pages innermost and (m, l, acc) online-softmax
scratch carried across them. The page indirection happens in the BlockSpec
index_map via **scalar prefetch** (pltpu.PrefetchScalarGridSpec): the page
table is prefetched to SMEM before the body runs, so the DMA engine fetches
k_pages[page_table[b, j]] directly — the gather costs nothing on top of the
streaming the dense kernel already does. Padding rows (-1) clamp to page 0
and are masked by the per-sequence length, so they never contribute.

Validated in interpret mode against ref.decode_attention over ragged lengths,
GQA group counts, and page sizes (tests/test_kernels.py).

Tensor parallelism: the kernel is **head-slice clean** — its grid iterates
(B·K, pages) and no computation crosses kv heads, so the serving executor
(serve/executor.py) calls it under ``shard_map`` with ``k_pages``/``v_pages``
holding only the shard's kv-head slice and ``q`` the matching query-head
block (head h = k·G + g is kv-head-major). The page table and lengths stay
replicated; per-head outputs are exact regardless of how heads are split, so
tp=N results concatenate bit-identically to tp=1.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import ref

NEG = -1e30


def paged_flash_decode(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                       page_table: jax.Array, lengths: jax.Array,
                       k_scale=None, v_scale=None, *,
                       interpret: bool) -> jax.Array:
    """One-token attention over a paged KV cache.

    q:          [B, H, hd]
    k_pages:    [P, K, pt, hd] physical page pool (P pages of pt tokens)
    v_pages:    [P, K, pt, hd]
    page_table: [B, max_pages] int32 page ids, -1 = unmapped
    lengths:    [B] int32 valid token counts
    k_scale:    optional [P, K] f32 per-(page, kv-head) dequant scales for
                an int8 pool (serve/kvquant.py); the page block dequantizes
                **in VMEM** — int8 rows × scale → f32 — before the f32
                softmax accumulation. The scale block rides the same
                prefetched page-table walk as its page (its BlockSpec
                index_map is the table lookup), so quantization adds one
                K-wide scale row per page, no extra gather.
    v_scale:    optional [P, K] f32 (must accompany ``k_scale``)
    interpret:  run the Pallas body in the interpreter (CPU) instead of
                compiling it with Mosaic (TPU).
    Returns [B, H, hd].
    """
    B, H, hd = q.shape
    P, K, pt, _ = k_pages.shape
    G = H // K
    max_pages = page_table.shape[1]
    scale = 1.0 / math.sqrt(hd)
    quant = k_scale is not None
    if quant != (v_scale is not None):
        raise ValueError("paged_flash_decode: k_scale and v_scale must be "
                         "given together")

    qr = q.reshape(B, K, G, hd).reshape(B * K, G, hd)
    # clamp padding rows: masked out by `lengths` below, but the index_map
    # must still name a resident page for the DMA
    table = jnp.maximum(page_table.astype(jnp.int32), 0)
    lengths_bk = jnp.repeat(lengths.astype(jnp.int32), K)    # [B*K]

    def kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref, *rest):
        if quant:
            ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
        else:
            o_ref, m_ref, l_ref, acc_ref = rest
        bk = pl.program_id(0)
        j = pl.program_id(1)

        @pl.when(j == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        seq_len = len_ref[bk]

        @pl.when(j * pt < seq_len)
        def _page():
            qb = q_ref[0].astype(jnp.float32)            # [G, hd]
            kb = k_ref[0, 0].astype(jnp.float32)         # [pt, hd]
            vb = v_ref[0, 0].astype(jnp.float32)
            if quant:
                # dequantize in VMEM: int8 page block × per-(page, head)
                # scale → f32, feeding the same f32 accumulation below
                kb = kb * head_scale(ks_ref, bk % K, pt)
                vb = vb * head_scale(vs_ref, bk % K, pt)
            s = jnp.dot(qb, kb.T, preferred_element_type=jnp.float32) * scale
            kpos = j * pt + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(kpos < seq_len, s, NEG)
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
            o_ref[0] = (acc_ref[...] /
                        jnp.maximum(l_ref[...], 1e-30)[:, None]).astype(o_ref.dtype)

    from jax.experimental.pallas import tpu as pltpu

    in_specs = [
        pl.BlockSpec((1, G, hd), lambda bk, j, tbl, lens: (bk, 0, 0)),
        # the page-table walk: physical page id from the prefetched table
        pl.BlockSpec((1, 1, pt, hd),
                     lambda bk, j, tbl, lens: (tbl[bk // K, j], bk % K, 0, 0)),
        pl.BlockSpec((1, 1, pt, hd),
                     lambda bk, j, tbl, lens: (tbl[bk // K, j], bk % K, 0, 0)),
    ]
    inputs = [table, lengths_bk, qr, k_pages, v_pages]
    if quant:
        # scale rows walk the same prefetched table as their pages, in a
        # [P, 1, K] view (see head_scale)
        in_specs += [pl.BlockSpec((1, 1, K), lambda bk, j, tbl, lens:
                                  (tbl[bk // K, j], 0, 0))] * 2
        inputs += [k_scale.reshape(P, 1, K), v_scale.reshape(P, 1, K)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,          # page_table, lengths_bk
        grid=(B * K, max_pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, G, hd), lambda bk, j, tbl, lens: (bk, 0, 0)),
        scratch_shapes=[pltpu.VMEM((G,), jnp.float32),
                        pltpu.VMEM((G,), jnp.float32),
                        pltpu.VMEM((G, hd), jnp.float32)],
    )

    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * K, G, hd), q.dtype),
        interpret=interpret,
        name="paged_decode_attention",
    )(*inputs)
    return out.reshape(B, H, hd)


def head_scale(scale_ref, kv_head, rows: int) -> jax.Array:
    """Pick ``kv_head``'s scale out of a page's ``(1, 1, K)`` scale block as
    a ``(rows, 1)`` column, ready to scale a ``(rows, hd)`` page block.

    The kernels see the ``[P, K]`` scales as ``[P, 1, K]``: Mosaic tiles the
    last two block dims by (8, 128) unless they span the whole array, so a
    ``(1, 1)`` block over ``[P, K]`` cannot lower, while ``(1, 1, K)`` spans
    both. The pick is a masked lane sum, since Mosaic has no dynamic lane
    load; the row is broadcast over sublanes first, since Mosaic cannot
    broadcast a ``(1, 1)`` value over sublanes and lanes at once."""
    row = jnp.broadcast_to(scale_ref[0], (rows, scale_ref.shape[-1]))
    lane = jax.lax.broadcasted_iota(jnp.int32, row.shape, 1)
    return jnp.sum(jnp.where(lane == kv_head, row, 0.0), axis=1,
                   keepdims=True)


def gather_pages(pages: jax.Array, page_table: jax.Array) -> jax.Array:
    """Materialize a dense [B, K, max_pages·pt, hd] cache from the page pool
    (test oracle + debugging; the kernel never does this)."""
    B, max_pages = page_table.shape
    _, K, pt, hd = pages.shape
    dense = jnp.take(pages, jnp.maximum(page_table, 0).reshape(-1), axis=0)
    dense = dense.reshape(B, max_pages, K, pt, hd)
    return jnp.transpose(dense, (0, 2, 1, 3, 4)).reshape(B, K, max_pages * pt, hd)


def dequant_pages(pages: jax.Array, page_scale: jax.Array) -> jax.Array:
    """Dequantize an int8 page pool dense: [P, K, pt, hd] × [P, K] → f32
    (test oracle + debugging; the kernel dequantizes per block in VMEM)."""
    return pages.astype(jnp.float32) * page_scale[:, :, None, None]


def paged_decode_attention_ref(q, k_pages, v_pages, page_table, lengths,
                               k_scale=None, v_scale=None):
    """Oracle: gather pages dense (dequantizing first when scales are
    given), then the masked-softmax decode oracle."""
    if k_scale is not None:
        k_pages = dequant_pages(k_pages, k_scale)
        v_pages = dequant_pages(v_pages, v_scale)
    k_dense = gather_pages(k_pages, page_table)
    v_dense = gather_pages(v_pages, page_table)
    return ref.decode_attention(q, k_dense, v_dense, lengths)

"""Pallas TPU kernel: paged-attention decode over a block-pool KV cache.

vLLM-style serving memory layout: K/V live in a shared pool of fixed-size
blocks (num_blocks, block_size, Hkv, hd) and each batch row names its blocks
through a block-table row.  Decode attends one query token per row over that
row's logical prefix, so the hot loop is pure HBM traffic — the kernel's job
is to stream exactly the live pages and nothing else (the dense-slab path
reads the full (max_batch, max_len) slab every step regardless of occupancy).

Schedule — ROW-PACKED and DOUBLE-BUFFERED:

  * grid = (ceil(B / R),): each grid step processes a PACK of R decode rows
    (``rows_per_pack``).  A lone (G, block_size) score tile badly underfills
    the MXU for small GQA groups (G = Hq/Hkv is 1-4 for the archs served
    here); packing R rows turns every per-kv-head matmul into
    (R*G, hd) @ (hd, R*block_size) — R× more sublanes AND R× more lanes per
    MXU pass.  The cross-row score quadrants are junk by construction and
    are masked to -inf together with the per-row length mask, so the online
    softmax over the packed key axis reduces to exactly the per-row result
    (masked terms contribute zero weight).
  * The block table and per-row lengths ride scalar prefetch (SMEM) so page
    DMA source indices are known before any data lands.  Pools stay
    HBM-resident (memory_space=ANY); each pack iteration streams one
    (block_size, Hkv, hd) page PER PACKED ROW into VMEM.
  * int8 pools: a (block_size, Hkv) scale page cannot be DMA'd on its own
    (Mosaic slices HBM only along 128-lane-aligned minor dims), so the
    wrapper gathers each pack's scales into one lane-major (Hkv, R*bs)
    tile per page, padded to 128 lanes, and the kernel streams one such
    tile per pack iteration.  A scale is per (key, head), so dequant is
    applied to score columns (q.(s k) = s (q.k)) and to probability
    columns before the PV matmul (sum p (s v) = sum (p s) v).
  * Page DMAs are DOUBLE-BUFFERED: two VMEM slots per operand, the copies
    for page p+1 start before the pack multiplies page p, so the next pages
    stream while the MXU works the current tile.
  * The page loop runs to the LONGEST packed row's page count; shorter
    rows' extra pages are fetched from a clamped block id and masked — the
    cost of packing, proportional to the length spread within a pack, is
    traded against the R× MXU fill (the serving decode roots zero dead
    rows' lengths so a retired slot never drags its pack; length-sorted
    packing for live rows is a queued follow-up).  Freed or never-
    allocated tail blocks beyond every packed row's length are never
    touched.

The jnp oracle in ref.py mirrors this packed layout (ragged last pack,
cross-row masking, int8 dequant inside the packed tile) so CPU tests pin
the kernel's tiling math, not just the attention result.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def default_rows_per_pack(batch: int, group: int) -> int:
    """Pack enough rows that the score tile's query dim (R*G) reaches the
    8-sublane fp32 tile, without padding tiny batches past themselves."""
    r = max(1, 8 // max(group, 1))
    return max(1, min(r, batch, 8))


def _kernel(bt_ref, len_ref, q_ref, kp_ref, vp_ref, *rest, block_size,
            scale, quant, rows_per_pack, max_blocks):
    if quant:
        (ksp_ref, vsp_ref, o_ref, k_buf, v_buf, sem_k, sem_v, ks_buf,
         vs_buf, sem_ks, sem_vs) = rest
    else:
        o_ref, k_buf, v_buf, sem_k, sem_v = rest
    bs = block_size
    r_pack = rows_per_pack
    i = pl.program_id(0)
    r0 = i * r_pack

    q = q_ref[...].astype(jnp.float32)  # (R, Hkv, G, hd)
    _, hkv, g, hd = q.shape
    # (Hkv, R*G, hd): kv-head-major so each head's packed queries multiply
    # that head's packed keys in one (R*G, hd) @ (hd, R*bs) MXU pass.
    qh = jnp.transpose(q, (1, 0, 2, 3)).reshape(hkv, r_pack * g, hd)

    lens = [len_ref[r0 + r] for r in range(r_pack)]  # R SMEM scalars
    n_pages = (functools.reduce(jnp.maximum, lens) + bs - 1) // bs

    def dma(buf, pool_ref, sem, slot, r, page):
        return pltpu.make_async_copy(pool_ref.at[page], buf.at[slot, r],
                                     sem.at[slot, r])

    def scale_dma(buf, src_ref, sem, slot, pp):
        return pltpu.make_async_copy(src_ref.at[i, pp], buf.at[slot],
                                     sem.at[slot])

    def page_copies(slot, p):
        pp = jnp.minimum(p, max_blocks - 1)
        for r in range(r_pack):
            # Clamp freed rows' -1 sentinels (and short rows' exhausted
            # tables) to block 0: the fetch is junk the mask hides.
            page = jnp.maximum(bt_ref[r0 + r, pp], 0)
            yield dma(k_buf, kp_ref, sem_k, slot, r, page)
            yield dma(v_buf, vp_ref, sem_v, slot, r, page)
        if quant:
            yield scale_dma(ks_buf, ksp_ref, sem_ks, slot, pp)
            yield scale_dma(vs_buf, vsp_ref, sem_vs, slot, pp)

    def start_pages(slot, p):
        for c in page_copies(slot, p):
            c.start()

    def wait_pages(slot, p):
        for c in page_copies(slot, p):
            c.wait()

    # Masks of the packed score tile: query n belongs to pack row n // G,
    # key column m to pack row m // bs — only the block diagonal is real.
    # Per-column row lengths are selected from the SMEM scalars per pack
    # row (Mosaic has no (R, bs) -> (1, R*bs) vector reshape).
    rq = jax.lax.broadcasted_iota(jnp.int32, (r_pack * g, 1), 0) // g
    rc = jax.lax.broadcasted_iota(jnp.int32, (1, r_pack * bs), 1) // bs
    same_row = rq == rc                                    # (R*G, R*bs)
    key_off = jax.lax.broadcasted_iota(jnp.int32, (1, r_pack * bs), 1) % bs
    len_cols = jnp.zeros((1, r_pack * bs), jnp.int32)
    for r in range(r_pack):
        len_cols = jnp.where(rc == r, lens[r], len_cols)

    @pl.when(n_pages > 0)
    def _warmup():
        start_pages(0, 0)

    def body(p, carry):
        acc, m, l = carry
        slot = jax.lax.rem(p, 2)

        @pl.when(p + 1 < n_pages)
        def _prefetch():
            start_pages(jax.lax.rem(p + 1, 2), p + 1)

        wait_pages(slot, p)
        k = k_buf[slot].astype(jnp.float32)  # (R, bs, Hkv, hd)
        v = v_buf[slot].astype(jnp.float32)
        # (Hkv, R*bs, hd): packed-key layout matching qh.
        kh = jnp.transpose(k, (2, 0, 1, 3)).reshape(hkv, r_pack * bs, hd)
        vh = jnp.transpose(v, (2, 0, 1, 3)).reshape(hkv, r_pack * bs, hd)
        s = jnp.einsum("knd,kmd->knm", qh, kh,
                       preferred_element_type=jnp.float32)
        s = s * scale
        if quant:  # per-(key, head) dequant scales, (Hkv, 1, R*bs)
            s = s * jnp.expand_dims(ks_buf[slot, :, : r_pack * bs], 1)
        pos = p * bs + key_off
        valid = jnp.logical_and(same_row, pos < len_cols)  # (R*G, R*bs)
        s = jnp.where(valid[None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        pexp = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(pexp, axis=-1, keepdims=True)
        pv = (pexp * jnp.expand_dims(vs_buf[slot, :, : r_pack * bs], 1)
              if quant else pexp)
        acc_new = acc * corr + jnp.einsum(
            "knm,kmd->knd", pv, vh, preferred_element_type=jnp.float32
        )
        return acc_new, m_new, l_new

    acc0 = jnp.zeros((hkv, r_pack * g, hd), jnp.float32)
    m0 = jnp.full((hkv, r_pack * g, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((hkv, r_pack * g, 1), jnp.float32)
    acc, _, l = jax.lax.fori_loop(0, n_pages, body, (acc0, m0, l0))
    out = acc / jnp.maximum(l, 1e-30)  # (Hkv, R*G, hd)
    o_ref[...] = jnp.transpose(
        out.reshape(hkv, r_pack, g, hd), (1, 0, 2, 3)
    ).astype(o_ref.dtype)


def scale_lanes(rows_per_pack: int, block_size: int) -> int:
    """Lane width of one pack's per-page scale tile (R*bs, padded to the
    128-lane tile so Mosaic can slice it out of HBM)."""
    return -(-rows_per_pack * block_size // 128) * 128


def _pack_scales(scales, block_tables, r_pack, lanes):
    """(N, bs, Hkv) scale pool -> (B/R, M, Hkv, lanes): for each pack and
    page, the packed rows' scales in the kernel's key-column order
    (column r*bs + t), zero-padded to ``lanes``."""
    b, m = block_tables.shape
    _, bs, hkv = scales.shape
    g = scales[jnp.maximum(block_tables, 0)]  # (B, M, bs, Hkv)
    g = jnp.transpose(g.reshape(b // r_pack, r_pack, m, bs, hkv),
                      (0, 2, 4, 1, 3)).reshape(b // r_pack, m, hkv,
                                               r_pack * bs)
    return jnp.pad(g, ((0, 0), (0, 0), (0, 0), (0, lanes - r_pack * bs)))


@functools.partial(
    jax.jit, static_argnames=("scale", "interpret", "rows_per_pack")
)
def paged_attention(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    block_tables: jax.Array,
    lengths: jax.Array,
    k_scales: jax.Array | None = None,
    v_scales: jax.Array | None = None,
    scale: float | None = None,
    interpret: bool = False,
    rows_per_pack: int | None = None,
) -> jax.Array:
    """q: (B, Hq, hd); pools (N, bs, Hkv, hd); block_tables (B, M) int32;
    lengths (B,) valid tokens per row (cache_len + 1).  Returns (B, Hq, hd).

    ``rows_per_pack=None`` picks R so the packed score tile's query dim
    reaches the 8-sublane tile (R = 8 // G, clamped to [1, min(B, 8)]).
    Ragged batches are padded with length-0 rows to a whole pack and
    sliced back — padding never DMAs past page 0 of block 0.
    """
    b, hq, hd = q.shape
    _, bs, hkv, _ = k_pages.shape
    m = block_tables.shape[1]
    g = hq // hkv
    if scale is None:
        scale = 1.0 / math.sqrt(hd)
    quant = k_scales is not None
    r_pack = (default_rows_per_pack(b, g) if rows_per_pack is None
              else max(1, rows_per_pack))

    b_pad = -(-b // r_pack) * r_pack
    if b_pad != b:
        pad = b_pad - b
        q = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
        block_tables = jnp.pad(block_tables, ((0, pad), (0, 0)),
                               constant_values=-1)
        lengths = jnp.pad(lengths, (0, pad))  # length 0: fully masked

    qg = q.reshape(b_pad, hkv, g, hd)  # head h = kv*G + gi (_gqa layout)
    kernel = functools.partial(
        _kernel, block_size=bs, scale=scale, quant=quant,
        rows_per_pack=r_pack, max_blocks=m,
    )
    in_specs = [
        pl.BlockSpec((r_pack, hkv, g, hd), lambda i, bt, ln: (i, 0, 0, 0)),
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    scratch = [  # double-buffered (2 slots) per-row page tiles
        pltpu.VMEM((2, r_pack, bs, hkv, hd), k_pages.dtype),
        pltpu.VMEM((2, r_pack, bs, hkv, hd), v_pages.dtype),
    ]
    operands = [block_tables, lengths, qg, k_pages, v_pages]
    scratch += [pltpu.SemaphoreType.DMA((2, r_pack))] * 2
    if quant:
        lanes = scale_lanes(r_pack, bs)
        in_specs += [
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ]
        scratch += [pltpu.VMEM((2, hkv, lanes), jnp.float32)] * 2
        scratch += [pltpu.SemaphoreType.DMA((2,))] * 2
        operands += [_pack_scales(sc, block_tables, r_pack, lanes)
                     for sc in (k_scales, v_scales)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b_pad // r_pack,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((r_pack, hkv, g, hd),
                               lambda i, bt, ln: (i, 0, 0, 0)),
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b_pad, hkv, g, hd), q.dtype),
        interpret=interpret,
    )(*operands)
    return out[:b].reshape(b, hq, hd)


def vmem_tiles(batch: int, num_q_heads: int, num_kv_heads: int,
               head_dim: int, block_size: int, *, dtype="float32",
               kv_dtype=None, quant: bool = False,
               rows_per_pack: int | None = None) -> list:
    """Static per-grid-step VMEM tile inventory for the packed decode
    kernel — one dict per resident buffer, mirroring the BlockSpecs /
    scratch_shapes in ``paged_attention`` above (keep in lockstep).

    ``buffers`` counts Pallas's automatic double-buffering of streamed
    BlockSpec operands (x2); the page rings carry their 2 DMA slots in
    their own leading dim, so they count once.  Consumed by
    repro.analysis.pallas_lint."""
    g = max(1, num_q_heads // max(1, num_kv_heads))
    hkv = max(1, num_kv_heads)
    r = (default_rows_per_pack(batch, g) if rows_per_pack is None
         else max(1, rows_per_pack))
    kv = kv_dtype or ("int8" if quant else dtype)
    tiles = [
        {"name": "q", "shape": (r, hkv, g, head_dim), "dtype": dtype,
         "buffers": 2},
        {"name": "out", "shape": (r, hkv, g, head_dim), "dtype": dtype,
         "buffers": 2},
        {"name": "k_page_ring", "shape": (2, r, block_size, hkv, head_dim),
         "dtype": kv, "buffers": 1},
        {"name": "v_page_ring", "shape": (2, r, block_size, hkv, head_dim),
         "dtype": kv, "buffers": 1},
        # fp32 softmax accumulators carried across the page loop.
        {"name": "acc", "shape": (hkv, r * g, head_dim), "dtype": "float32",
         "buffers": 1},
        {"name": "m_l", "shape": (2, hkv, r * g, 1), "dtype": "float32",
         "buffers": 1},
    ]
    if quant:
        lanes = scale_lanes(r, block_size)
        tiles += [
            {"name": "k_scale_ring", "shape": (2, hkv, lanes),
             "dtype": "float32", "buffers": 1},
            {"name": "v_scale_ring", "shape": (2, hkv, lanes),
             "dtype": "float32", "buffers": 1},
        ]
    return tiles

"""Pallas TPU kernel: latent-attention flash-decode over a stacked latent
cache (DeepSeek-V2's absorbed decode, ``repro.models.mla``).

One latent query per head and sequence attends over that sequence's
cached latent rows, positions 0..pos:

    q:     (B, H, W)        the query in latent space, zero past its width
    stack: (U, B, S, W)     the layer scan's latent cache, every unit
    unit:  ()  int32        the layer's index in the stack
    pos:   (B,) int32       each sequence's position; rows past it masked

and returns the softmax-weighted sum of the rows, (B, H, W) in float32.

Grid (B / bb, S / bs), rows innermost.  A block is bb sequences' rows
[k * bs, (k + 1) * bs) of the layer: the scores and the weighted sum both
come from that one read, with the online-softmax state (m, l, acc) in
VMEM scratch across the row blocks.  ``unit`` and each sequence block's
last needed row block ride scalar prefetch.  Row blocks run from the last
to the first, and the index map clamps each to the last needed one: the
grid steps past a sequence block's positions come first, repeat that
block's index so that they start no DMA, and ``pl.when`` skips their
compute.  So the copy of a sequence block's first block overlaps the
previous block's compute, as every other copy does.  The kernel takes the
whole stack and picks the layer in its index map: a slice of the stack
passed to a custom call would be copied out, the layer's every row, each
step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

#: Rows of a sequence a block holds at most, and the bytes of latent rows
#: a block moves at most.  Each sequence's share of a block has a fixed
#: cost besides its rows, and rows past a position up to the block's end
#: are read for nothing.  On a v5e at DeepSeek-V2-Lite's widths, blocks
#: of 8 sequences x 512 rows (5.2 MB) were the fastest at five of six
#: contexts from 257 to 2,032: 256-row blocks were 9% faster at 768,
#: where 512-row blocks read 1,024 rows, and 14-19% slower elsewhere;
#: 4 x 512 was up to 10% slower.
MAX_BLOCK_ROWS = 512
BLOCK_BYTES = 8 << 20


def _divisor(n: int, most: int, multiple: int = 1) -> int | None:
    """The largest divisor of ``n`` that is a multiple of ``multiple`` and
    at most ``most``, or None."""
    for d in range(min(n, most), 0, -1):
        if n % d == 0 and d % multiple == 0:
            return d
    return None


def blocks(b: int, s: int, w: int, itemsize: int) -> tuple[int, int]:
    """(sequences, rows) of a block, from the shapes: rows a multiple of
    the 16-row tile that divides S (all of S where none does), then as
    many sequences dividing B as keep the block within ``BLOCK_BYTES``."""
    bs = _divisor(s, MAX_BLOCK_ROWS, 16) or s
    bb = _divisor(b, max(1, BLOCK_BYTES // (bs * w * itemsize))) or 1
    return bb, bs


def _kernel(unit_ref, last_ref, pos_ref,      # scalar prefetch (SMEM)
            q_ref, lat_ref,                   # VMEM blocks
            o_ref,
            m_ref, l_ref, acc_ref,            # VMEM scratch
            *, bb: int, bs: int, scale: float):
    del unit_ref                              # used by the index map only
    i, s = pl.program_id(0), pl.program_id(1)
    k = pl.num_programs(1) - 1 - s            # this step's row block

    @pl.when(s == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(k <= last_ref[i])
    def _block():
        for j in range(bb):
            pos = pos_ref[i * bb + j]

            @pl.when(k * bs <= pos)
            def _sequence():
                q = q_ref[j]                              # (H, W)
                rows = lat_ref[0, j].astype(q.dtype)      # (bs, W)
                sco = jax.lax.dot_general(
                    q, rows, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                idx = k * bs + jax.lax.broadcasted_iota(
                    jnp.int32, sco.shape, 1)
                sco = jnp.where(idx <= pos, sco, NEG_INF)
                m_prev = m_ref[j]
                m_new = jnp.maximum(m_prev, sco.max(axis=1, keepdims=True))
                p = jnp.exp(sco - m_new)
                corr = jnp.exp(m_prev - m_new)
                l_ref[j] = l_ref[j] * corr + p.sum(axis=1, keepdims=True)
                acc_ref[j] = acc_ref[j] * corr + jax.lax.dot_general(
                    p.astype(q.dtype), rows, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                m_ref[j] = m_new

    @pl.when(s == pl.num_programs(1) - 1)
    def _flush():
        o_ref[...] = acc_ref[...] / l_ref[...]


@functools.partial(jax.jit, static_argnames=(
    "scale", "block_b", "block_s", "interpret"))
def mla_decode_attention(q: jnp.ndarray, stack: jnp.ndarray,
                         unit: jnp.ndarray, pos: jnp.ndarray, *,
                         scale: float, block_b: int | None = None,
                         block_s: int | None = None,
                         interpret: bool = False) -> jnp.ndarray:
    """Softmax(scale q . rows) . rows over each sequence's rows 0..pos of
    layer ``unit`` of ``stack``: (B, H, W) float32.  ``pos`` < S.  Block
    sizes come from the shapes (``blocks``) unless given; they must
    divide B and S."""
    b, h, w = q.shape
    s = stack.shape[2]
    bb, bs = blocks(b, s, w, stack.dtype.itemsize)
    bb, bs = block_b or bb, block_s or bs
    n_b, n_s = b // bb, s // bs
    pos = pos.astype(jnp.int32)
    last = jnp.minimum(pos.reshape(n_b, bb).max(axis=1) // bs, n_s - 1)
    unit = jnp.reshape(unit, (1,)).astype(jnp.int32)

    q_bytes = bb * h * w * q.dtype.itemsize
    lat_bytes = bb * bs * w * stack.dtype.itemsize
    out_bytes = bb * h * w * 4
    vmem = 2 * (q_bytes + lat_bytes + out_bytes) + out_bytes + (8 << 20)
    return pl.pallas_call(
        functools.partial(_kernel, bb=bb, bs=bs, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n_b, n_s),
            in_specs=[
                pl.BlockSpec((bb, h, w), lambda i, j, u, last, p: (i, 0, 0)),
                pl.BlockSpec((1, bb, bs, w), lambda i, j, u, last, p: (
                    u[0], i, jnp.minimum(n_s - 1 - j, last[i]), 0)),
            ],
            out_specs=pl.BlockSpec((bb, h, w),
                                   lambda i, j, u, last, p: (i, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((bb, h, 1), jnp.float32),
                pltpu.VMEM((bb, h, 1), jnp.float32),
                pltpu.VMEM((bb, h, w), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, w), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem),
        name="mla_decode_attention",
        interpret=interpret,
    )(unit, last, pos, q, stack)

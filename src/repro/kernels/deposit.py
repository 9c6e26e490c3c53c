"""Pallas TPU kernel: scatter-add work deposit (the fleet-sim hot bin).

The fused fleet simulator bins millions of chunked-prefill token
deposits into the dense ``(plans * stations, time-bins)`` work tensor on
every fixed-point iteration.  A scatter is MXU-hostile, so the kernel
uses the standard one-hot-matmul trick: for each block of chunks and
each output time-tile, build the (chunk, row) and (chunk, bin-in-tile)
one-hot matrices and accumulate ``onehot_rows.T @ (vals * onehot_bins)``
— a dense (bc, S) x (bc, bt) contraction the MXU eats, with the full
row axis resident in a VMEM scratch accumulator.

Tiling: grid (rows/br, T/bt, C/bc) with the chunk axis innermost, so a
VMEM scratch (br, bt) accumulates over chunk blocks and flushes once per
(row-tile, time-tile).  Chunks outside a tile contribute zero rows in
the one-hots (no masking pass needed), and chunk padding points at
column ``n_cols_pad`` which no tile covers.  The row tiling bounds VMEM
at ``br * bt`` regardless of the fleet size (the fused fleet simulator
deposits into F * rows planes that can reach tens of thousands of rows).

Off-TPU the one-hot matmul is hopeless (interpret mode runs the kernel
body in Python), so :func:`deposit_segments` offers the CPU/GPU scatter
relief: the same COO triples as a row-bucketed sorted ``segment_sum``,
bitwise identical to the :func:`repro.kernels.ref.deposit_ref` oracle
(see its docstring for when it actually pays).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _deposit_kernel(rows_ref, cols_ref, vals_ref, o_ref, acc_ref, *,
                    n_chunk_blocks: int):
    """One (row-tile, time-tile, chunk-block) grid step.

    ``rows_ref`` is a lane vector (1, bc); ``cols_ref`` and ``vals_ref``
    are sublane vectors (bc, 1), so both one-hots come out in the
    layout the MXU contraction wants without a transpose.
    """
    r = pl.program_id(0)
    t = pl.program_id(1)
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    rows = rows_ref[...]                                 # (1, bc) int32
    cols = cols_ref[...]                                 # (bc, 1) int32
    vals = vals_ref[...]                                 # (bc, 1)
    bc = cols.shape[0]
    br, bt = acc_ref.shape
    dtype = acc_ref.dtype
    # Chunks outside this (row, time) tile match no one-hot lane: zero
    # contribution, no separate masking pass.
    iota_r = jax.lax.broadcasted_iota(jnp.int32, (br, bc), 0)
    oh_rows = ((rows - r * br) == iota_r).astype(dtype)  # (br, bc)
    iota_t = jax.lax.broadcasted_iota(jnp.int32, (bc, bt), 1)
    oh_cols = ((cols - t * bt) == iota_t).astype(dtype)  # (bc, bt)
    # HIGHEST: a default-precision f32 matmul rounds vals to bf16.
    acc_ref[...] += jnp.dot(oh_rows, vals * oh_cols,
                            preferred_element_type=dtype,
                            precision=jax.lax.Precision.HIGHEST)

    @pl.when(c == n_chunk_blocks - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _pad_to(x: jnp.ndarray, mult: int, fill) -> jnp.ndarray:
    pad = (-x.shape[0]) % mult
    if pad == 0:
        return x
    return jnp.concatenate([x, jnp.full(pad, fill, dtype=x.dtype)])


@functools.partial(
    jax.jit, static_argnames=("n_rows", "n_cols", "bucketed"))
def deposit_segments(
    rows: jnp.ndarray,            # (C,) int, in [0, n_rows)
    cols: jnp.ndarray,            # (C,) int, in [0, n_cols)
    vals: jnp.ndarray,            # (C,) float
    n_rows: int,
    n_cols: int,
    bucketed: bool = True,
) -> jnp.ndarray:
    """Row-bucketed segment-sum deposit — the non-TPU scatter relief.

    Off-TPU the fleet simulator's hot bin is a bare
    ``zeros.at[flat].add(vals)`` — a serial scatter on XLA:CPU whose
    per-update random access hurts once the target ids shuffle.  This
    path instead presents the same deposit as a sorted
    :func:`jax.ops.segment_sum`, which XLA handles with the
    sorted-segment reduction (~3x the scatter's throughput once the ids
    are sorted).  Measured head-to-head by ``bench_fleet``'s
    ``deposit_stage``: it wins on mid-size shuffled tables, while the
    fleet's statically row-grouped chunk table keeps the inline scatter
    cache-friendly enough that this stays the opt-in
    ``deposit_impl="segments"`` rather than the default.

    The sort is the whole battle: a two-operand (key, payload) sort —
    ``argsort`` or ``sort_key_val`` — costs ~8x a single-operand key
    sort on XLA:CPU and would eat the relief.  So with ``bucketed=True``
    the chunk index is **packed into the low bits of the flat id**
    (``flat << ceil(log2(C)) | i``) and one single-operand int64 sort
    yields both the sorted segment ids (high bits) and the gather order
    (low bits).  The packing doubles as a stability guarantee: ties in
    the flat id sort by original chunk position, so per-(row, bin)
    deposits apply in table order.  Because XLA scatter/segment
    additions into one accumulator apply in update order, the result is
    **bitwise identical** to :func:`deposit_ref` (pinned by
    ``tests/test_fleet_perf.py``), which is what lets the fused fleet
    trace stay bit-identical when this path replaces the inline scatter.
    On worlds so large that ``n_rows * n_cols * C`` overflows the packed
    int64, the path degrades to a stable two-operand sort.

    Returns (n_rows, n_cols) in vals.dtype.
    """
    if rows.shape != cols.shape or rows.shape != vals.shape:
        raise ValueError(
            f"shape mismatch {rows.shape} / {cols.shape} / {vals.shape}")
    n_flat = n_rows * n_cols
    idx = jnp.int32 if n_flat <= jnp.iinfo(jnp.int32).max else jnp.int64
    flat = rows.astype(idx) * n_cols + cols.astype(idx)
    if n_flat > jnp.iinfo(flat.dtype).max:
        raise ValueError(
            f"deposit target {n_rows}x{n_cols} overflows {flat.dtype} "
            "flat indices (enable jax x64)")
    n = rows.shape[0]
    shift = max(1, int(n - 1).bit_length())
    if bucketed and n > 0 and n_flat <= (1 << (63 - shift)):
        packed = jnp.sort((flat.astype(jnp.int64) << shift)
                          | jnp.arange(n, dtype=jnp.int64))
        ids = packed >> shift
        vals = vals[packed & ((1 << shift) - 1)]
        flat = ids.astype(idx)
    elif bucketed:
        order = jnp.argsort(flat, stable=True)
        flat, vals = flat[order], vals[order]
    out = jax.ops.segment_sum(vals, flat, num_segments=n_flat,
                              indices_are_sorted=bucketed)
    return out.reshape(n_rows, n_cols)


@functools.partial(
    jax.jit,
    static_argnames=("n_rows", "n_cols", "block_r", "block_c", "block_t",
                     "interpret"),
)
def deposit(
    rows: jnp.ndarray,            # (C,) int, in [0, n_rows)
    cols: jnp.ndarray,            # (C,) int, in [0, n_cols)
    vals: jnp.ndarray,            # (C,) float
    n_rows: int,
    n_cols: int,
    block_r: int = 512,
    block_c: int = 512,
    block_t: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    """Dense scatter-add: out[rows[i], cols[i]] += vals[i].

    Returns (n_rows, n_cols) in vals.dtype.
    """
    if rows.shape != cols.shape or rows.shape != vals.shape:
        raise ValueError(
            f"shape mismatch {rows.shape} / {cols.shape} / {vals.shape}")
    if rows.shape[0] == 0:
        # Zero chunk blocks would leave the output buffer unwritten.
        return jnp.zeros((n_rows, n_cols), dtype=vals.dtype)
    # Tiles obey the TPU's (8, 128) layout: rows pad to a multiple of 8,
    # bins and chunks to multiples of 128.
    br = min(block_r, _round_up(n_rows, 8))
    n_rows_pad = _round_up(n_rows, br)
    bt = min(block_t, _round_up(n_cols, 128))
    n_cols_pad = _round_up(n_cols, bt)
    bc = min(block_c, _round_up(rows.shape[0], 128))
    # Padding chunks target column n_cols_pad (outside every tile) with
    # zero weight, so they deposit nothing.
    rows_p = _pad_to(rows.astype(jnp.int32), bc, 0)
    cols_p = _pad_to(cols.astype(jnp.int32), bc, n_cols_pad)
    vals_p = _pad_to(vals, bc, 0)
    n_chunks = rows_p.shape[0]
    n_blocks = n_chunks // bc
    grid = (n_rows_pad // br, n_cols_pad // bt, n_blocks)

    out = pl.pallas_call(
        functools.partial(_deposit_kernel, n_chunk_blocks=n_blocks),
        grid=grid,
        # Block indices are int32 even when the caller traces under x64
        # (the fused fleet launch does): Mosaic rejects i64 indices.
        in_specs=[
            pl.BlockSpec((1, bc), lambda r, t, c: (jnp.int32(0), c)),
            pl.BlockSpec((bc, 1), lambda r, t, c: (c, jnp.int32(0))),
            pl.BlockSpec((bc, 1), lambda r, t, c: (c, jnp.int32(0))),
        ],
        out_specs=pl.BlockSpec((br, bt), lambda r, t, c: (r, t)),
        out_shape=jax.ShapeDtypeStruct((n_rows_pad, n_cols_pad),
                                       vals.dtype),
        scratch_shapes=[pltpu.VMEM((br, bt), vals.dtype)],
        interpret=interpret,
    )(rows_p.reshape(1, n_chunks), cols_p.reshape(n_chunks, 1),
      vals_p.reshape(n_chunks, 1))
    return out[:n_rows, :n_cols]

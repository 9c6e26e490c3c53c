"""Pallas TPU kernel: the decode step's expert FFN over only the experts
that the batch routes to, read straight from the layer scan's stacks.

    xs:     (E, C, d)       one layer's capacity-padded expert buckets
    w_gate: (U, E, d, f)    every unit's gate projections (the stack)
    w_up:   (U, E, d, f)
    w_down: (U, E, f, d)
    unit:   ()  int32       the layer's index in the stacks
    ids:    (n,) int32      the experts hit, ascending, padded to the
                            static slot count by repeating the last one
    n_hit:  ()  int32       how many of ``ids`` are real

and returns the SwiGLU ``(silu(x Wg) * (x Wu)) Wd`` of each hit expert's
bucket, (E, C, d) in the buckets' dtype, zero for the experts not hit:
what ``repro.models.moe.expert_ffn`` gives for the layer, having read
only the hit experts' weights.

Grid (slots,).  A step reads one hit expert's three whole matrices,
chosen by the index maps from ``unit`` and ``ids`` in scalar prefetch,
while the next hit expert's are copied in.  The steps of the padded slots
repeat the last real step's blocks, so they start no DMA, and
``pl.when`` skips their compute.  The buckets and the output stay in
VMEM over the whole grid: the output is zeroed once and written back
once, so the experts with no copy read zero.  The kernel takes the whole
stacks and picks the layer in its index maps: a slice of a stack passed
to a custom call would be copied out, the layer's every expert, each
step.

Blocks: whole experts (3 x 5.8 MB at DeepSeek-MoE-16B's d 2048 and d_ff
1408, 35 MB double-buffered), one grid step an expert.  d_ff = 1408 is
11 x 128, so the only narrower tiles are 128 columns, 11 grid steps of
~0.35 us each against ~1.9 us of copying apiece at the HBM rate.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(unit_ref, ids_ref, n_ref,         # scalar prefetch (SMEM)
            x_ref, wg_ref, wu_ref, wd_ref,    # VMEM blocks
            o_ref):
    del unit_ref                              # used by the index maps only
    s = pl.program_id(0)

    @pl.when(s == 0)
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(s < n_ref[0])
    def _expert():
        e = ids_ref[s]
        x = x_ref[e]                                           # (C, d)
        g = jnp.dot(x, wg_ref[0, 0].astype(x.dtype),
                    preferred_element_type=jnp.float32)
        u = jnp.dot(x, wu_ref[0, 0].astype(x.dtype),
                    preferred_element_type=jnp.float32)
        h = (jax.nn.silu(g) * u).astype(x.dtype)               # (C, f)
        o_ref[e] = jnp.dot(h, wd_ref[0, 0].astype(x.dtype),
                           preferred_element_type=jnp.float32
                           ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def expert_ffn_hit(xs: jnp.ndarray, w_gate: jnp.ndarray, w_up: jnp.ndarray,
                   w_down: jnp.ndarray, unit: jnp.ndarray, ids: jnp.ndarray,
                   n_hit: jnp.ndarray, *, interpret: bool = False
                   ) -> jnp.ndarray:
    """SwiGLU of the buckets of experts ``ids[:n_hit]`` with layer
    ``unit``'s weights, zero elsewhere: (E, C, d) in ``xs.dtype``."""
    e, c, d = xs.shape
    f = w_gate.shape[-1]
    unit = jnp.reshape(unit, (1,)).astype(jnp.int32)
    n_hit = jnp.reshape(n_hit, (1,)).astype(jnp.int32)

    def expert(s, u, ids, n):
        return u[0], ids[s], 0, 0

    w_bytes = 3 * d * f * w_gate.dtype.itemsize
    xo_bytes = 2 * e * c * d * xs.dtype.itemsize
    vmem = 2 * (w_bytes + xo_bytes) + (8 << 20)
    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(ids.shape[0],),
            in_specs=[
                pl.BlockSpec((e, c, d), lambda s, u, ids, n: (0, 0, 0)),
                pl.BlockSpec((1, 1, d, f), expert),
                pl.BlockSpec((1, 1, d, f), expert),
                pl.BlockSpec((1, 1, f, d), expert),
            ],
            out_specs=pl.BlockSpec((e, c, d), lambda s, u, ids, n: (0, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((e, c, d), xs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=vmem),
        name="expert_ffn_hit",
        interpret=interpret,
    )(unit, ids.astype(jnp.int32), n_hit, xs, w_gate, w_up, w_down)

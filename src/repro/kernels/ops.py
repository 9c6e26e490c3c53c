"""Jit'd public wrappers around the Pallas kernels.

``interpret`` defaults to True off-TPU so the same call sites work in this
CPU container (Pallas interpret mode executes the kernel body in Python);
on TPU the compiled Mosaic kernels run.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from .decode_attn import decode_attention as _decode_attention
from .deposit import deposit as _deposit
from .deposit import deposit_segments as _deposit_segments
from .mla_decode import mla_decode_attention as _mla_decode_attention
from .moe_decode import expert_ffn_hit as _expert_ffn_hit
from .moe_gmm import gmm as _gmm


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def timed_call(fn, *args, iters: int = 3, warmup: int = 1) -> float:
    """Best-of-``iters`` blocked wall time of ``fn(*args)``, seconds.

    The measurement primitive behind ``repro.core.calibration`` and the
    kernel benchmarks: warmup calls absorb compilation, then each timed
    call blocks on the result so async dispatch cannot hide the work.
    """
    for _ in range(max(warmup, 0)):
        jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(max(iters, 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def gmm(x: jnp.ndarray, w: jnp.ndarray, *, block_c: int = 128,
        block_n: int = 128, block_k: int = 512,
        interpret: bool | None = None) -> jnp.ndarray:
    """Grouped expert matmul (E,C,K)x(E,K,N)->(E,C,N)."""
    if interpret is None:
        interpret = not on_tpu()
    return _gmm(x, w, block_c=block_c, block_n=block_n, block_k=block_k,
                interpret=interpret)


def expert_ffn_pallas(params: dict, xs: jnp.ndarray, compute_dtype,
                      interpret: bool | None = None) -> jnp.ndarray:
    """Drop-in replacement for ``repro.models.moe.expert_ffn`` using gmm."""
    xs = xs.astype(compute_dtype)
    wg = params["w_gate"].astype(compute_dtype)
    wu = params["w_up"].astype(compute_dtype)
    wd = params["w_down"].astype(compute_dtype)
    gate = jax.nn.silu(gmm(xs, wg, interpret=interpret))
    up = gmm(xs, wu, interpret=interpret)
    return gmm(gate * up, wd, interpret=interpret)


def expert_ffn_hit(xs: jnp.ndarray, w_gate: jnp.ndarray, w_up: jnp.ndarray,
                   w_down: jnp.ndarray, unit: jnp.ndarray, ids: jnp.ndarray,
                   n_hit: jnp.ndarray, *,
                   interpret: bool | None = None) -> jnp.ndarray:
    """The decode expert FFN of the experts ``ids[:n_hit]`` with layer
    ``unit`` of the (U,E,...) weight stacks: (E,C,d) out, zero for the
    experts not hit."""
    if interpret is None:
        interpret = not on_tpu()
    return _expert_ffn_hit(xs, w_gate, w_up, w_down, unit, ids, n_hit,
                           interpret=interpret)


def deposit(rows: jnp.ndarray, cols: jnp.ndarray, vals: jnp.ndarray,
            n_rows: int, n_cols: int, *, block_r: int = 512,
            block_c: int = 512, block_t: int = 256,
            interpret: bool | None = None) -> jnp.ndarray:
    """Scatter-add work deposit: (n_rows, n_cols) dense from COO triples."""
    if interpret is None:
        interpret = not on_tpu()
    return _deposit(rows, cols, vals, n_rows, n_cols, block_r=block_r,
                    block_c=block_c, block_t=block_t, interpret=interpret)


def deposit_segments(rows: jnp.ndarray, cols: jnp.ndarray,
                     vals: jnp.ndarray, n_rows: int, n_cols: int, *,
                     bucketed: bool = True) -> jnp.ndarray:
    """Row-bucketed segment-sum deposit (non-TPU scatter relief).

    Bitwise identical to ``repro.kernels.ref.deposit_ref``; the fused
    fleet simulator's opt-in off-TPU deposit (``deposit_impl="segments"``,
    timed against the inline scatter by ``bench_fleet``).
    """
    return _deposit_segments(rows, cols, vals, n_rows, n_cols,
                             bucketed=bucketed)


def decode_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                     pos: jnp.ndarray, *, block_s: int = 512,
                     interpret: bool | None = None) -> jnp.ndarray:
    """GQA flash-decode over a (B,Hkv,S,hd) KV cache: (B,Hkv,G,hd) out.

    Calibration times it (``core.calibration.measure_components``); the
    served decode step runs ``attention._decode_core`` instead."""
    if interpret is None:
        interpret = not on_tpu()
    return _decode_attention(q, k, v, pos, block_s=block_s,
                             interpret=interpret)


def mla_decode_attention(q: jnp.ndarray, stack: jnp.ndarray,
                         unit: jnp.ndarray, pos: jnp.ndarray, *,
                         scale: float, block_b: int | None = None,
                         block_s: int | None = None,
                         interpret: bool | None = None) -> jnp.ndarray:
    """Latent-attention flash-decode over layer ``unit`` of a stacked
    latent cache: (B,H,W) f32 out."""
    if interpret is None:
        interpret = not on_tpu()
    return _mla_decode_attention(q, stack, unit, pos, scale=scale,
                                 block_b=block_b, block_s=block_s,
                                 interpret=interpret)

"""GQA attention: chunked (flash-style) training path + KV-cache decode.

The training path is an online-softmax two-level scan (query chunks x KV
chunks) so the S x S score matrix is never materialized — peak temp memory
is O(q_chunk * kv_chunk) per head, and HLO size is O(1) in sequence
length.  Causally fully-masked KV blocks are still computed (XLA scans
cannot skip iterations), which overcounts attention FLOPs by ~2x — this is
accounted for in the roofline's MODEL_FLOPS/HLO_FLOPs ratio and is the
motivation for the Pallas decode/splash kernels in ``repro.kernels``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .layers import apply_rope, normal_init, out_proj_init

NEG_INF = -1e30


def attn_init(key, cfg: ModelConfig, dtype) -> dict:
    kq, kk, kv, ko = jax.random.split(key, 4)
    p = {
        "w_q": normal_init(kq, (cfg.d_model, cfg.q_dim), dtype),
        "w_k": normal_init(kk, (cfg.d_model, cfg.kv_dim), dtype),
        "w_v": normal_init(kv, (cfg.d_model, cfg.kv_dim), dtype),
        "w_o": out_proj_init(ko, (cfg.q_dim, cfg.d_model), dtype, cfg.n_layers),
    }
    if cfg.qkv_bias:
        p["b_q"] = jnp.zeros((cfg.q_dim,), dtype)
        p["b_k"] = jnp.zeros((cfg.kv_dim,), dtype)
        p["b_v"] = jnp.zeros((cfg.kv_dim,), dtype)
    return p


@jax.named_scope("attn.qkv")
def _project_qkv(cfg: ModelConfig, params, x, positions, compute_dtype):
    """x: (B, S, d) -> q (B,S,Hq,hd), k/v (B,S,Hkv,hd), with RoPE unless
    the model has no position embedding (``cfg.nope``)."""
    b, s, _ = x.shape
    x = x.astype(compute_dtype)
    q = x @ params["w_q"].astype(compute_dtype)
    k = x @ params["w_k"].astype(compute_dtype)
    v = x @ params["w_v"].astype(compute_dtype)
    if cfg.qkv_bias:
        q = q + params["b_q"].astype(compute_dtype)
        k = k + params["b_k"].astype(compute_dtype)
        v = v + params["b_v"].astype(compute_dtype)
    q = q.reshape(b, s, cfg.n_heads, cfg.head_dim)
    k = k.reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    if not cfg.nope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def softmax_scale(cfg: ModelConfig) -> float:
    """``attention_multiplier`` where the model sets one, else
    head_dim^-0.5."""
    return cfg.attention_multiplier or cfg.head_dim ** -0.5


def _softcap(s, cap: float):
    return cap * jnp.tanh(s / cap) if cap > 0 else s


def _pick_chunk(s: int, target: int) -> int:
    """Largest divisor of ``s`` that is <= target (trace-time, static)."""
    c = min(target, s)
    while s % c:
        c -= 1
    return c


@jax.named_scope("attn.core")
def flash_attention(
    cfg: ModelConfig,
    q: jnp.ndarray,          # (B, S, Hq, hd)
    k: jnp.ndarray,          # (B, S, Hkv, hd)
    v: jnp.ndarray,
    q_positions: jnp.ndarray,   # (B, S) global positions of queries
    kv_positions: jnp.ndarray,  # (B, S)
    scale: float | None = None,  # None => hd ** -0.5
) -> jnp.ndarray:
    """Causal online-softmax attention, chunked along both S axes.

    ``v`` may be narrower than ``q``/``k`` (latent attention: qk width
    192, v width 128); the output has v's width.

    With ``cfg.flash_vjp`` the backward pass recomputes probabilities
    chunk-wise (custom VJP) instead of letting scan-AD save every (qc,kc)
    probability block — which otherwise materializes the full S^2 attention
    matrix per layer during backprop and dominates the memory roofline of
    every *train* cell.
    """
    if cfg.flash_vjp:
        out, _ = _flash_vjp_fn(cfg, scale)(q, k, v, q_positions, kv_positions)
        return out
    out, _ = _flash_fwd(cfg, q, k, v, q_positions, kv_positions, scale)
    return out


def _flash_fwd(
    cfg: ModelConfig,
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    q_positions: jnp.ndarray,
    kv_positions: jnp.ndarray,
    scale: float | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (out (B,S,Hq,dv), lse (B,Hkv,G,S) log-sum-exp per query)."""
    b, s, hq, hd = q.shape
    hkv, dv = k.shape[2], v.shape[-1]
    g = hq // hkv
    qc = _pick_chunk(s, cfg.attn_q_chunk)
    kc = _pick_chunk(s, cfg.attn_kv_chunk)
    nq, nk = s // qc, s // kc
    scale = hd ** -0.5 if scale is None else scale

    # (B, Hkv, G, S, hd) view of q; K/V stay (B, Hkv, S, hd).
    qg = q.reshape(b, s, hkv, g, hd).transpose(0, 2, 3, 1, 4)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    q_chunks = qg.reshape(b, hkv, g, nq, qc, hd).transpose(3, 0, 1, 2, 4, 5)
    qpos_chunks = q_positions.reshape(b, nq, qc).transpose(1, 0, 2)
    k_chunks = kt.reshape(b, hkv, nk, kc, hd).transpose(2, 0, 1, 3, 4)
    v_chunks = vt.reshape(b, hkv, nk, kc, dv).transpose(2, 0, 1, 3, 4)
    kpos_chunks = kv_positions.reshape(b, nk, kc).transpose(1, 0, 2)

    def q_step(_, q_in):
        q_blk, qpos = q_in        # (B,Hkv,G,qc,hd), (B,qc)

        def kv_step(carry, kv_in):
            m, l, acc = carry
            k_blk, v_blk, kpos = kv_in
            sco = jnp.einsum(
                "bngqd,bnkd->bngqk", q_blk, k_blk,
                preferred_element_type=jnp.float32,
            ) * scale
            sco = _softcap(sco, cfg.attn_logit_softcap)
            mask = qpos[:, None, None, :, None] >= kpos[:, None, None, None, :]
            if cfg.sliding_window > 0:
                near = (qpos[:, None, None, :, None]
                        - kpos[:, None, None, None, :]) < cfg.sliding_window
                mask = mask & near
            sco = jnp.where(mask, sco, NEG_INF)
            m_new = jnp.maximum(m, sco.max(axis=-1))
            p = jnp.exp(sco - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bngqk,bnkd->bngqd", p.astype(v_blk.dtype), v_blk,
                preferred_element_type=jnp.float32,
            )
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, hkv, g, qc), NEG_INF, dtype=jnp.float32)
        l0 = jnp.zeros((b, hkv, g, qc), dtype=jnp.float32)
        a0 = jnp.zeros((b, hkv, g, qc, dv), dtype=jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0), (k_chunks, v_chunks, kpos_chunks)
        )
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        lse = m + jnp.log(jnp.maximum(l, 1e-30))
        return None, (out.astype(q.dtype), lse)

    _, (out_chunks, lse_chunks) = jax.lax.scan(
        q_step, None, (q_chunks, qpos_chunks)
    )
    # (nq, B, Hkv, G, qc, dv) -> (B, S, Hq, dv)
    out = out_chunks.transpose(1, 2, 3, 0, 4, 5).reshape(b, hkv, g, s, dv)
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, s, hq, dv)
    lse = lse_chunks.transpose(1, 2, 3, 0, 4).reshape(b, hkv, g, s)
    return out, lse


def _mask_block(cfg: ModelConfig, qpos, kpos):
    """(B, qc, kc) bool mask for one chunk pair (causal [+ window])."""
    m = qpos[:, :, None] >= kpos[:, None, :]
    if cfg.sliding_window > 0:
        m = m & ((qpos[:, :, None] - kpos[:, None, :]) < cfg.sliding_window)
    return m


@functools.lru_cache(maxsize=None)
def _flash_vjp_fn(cfg: ModelConfig, scale: float | None = None):
    """custom-VJP flash attention: O(qc*kc) backward temporaries."""
    if cfg.attn_logit_softcap > 0:
        raise NotImplementedError(
            "flash_vjp does not implement the softcap derivative"
        )

    @jax.custom_vjp
    def flash(q, k, v, qpos, kpos):
        return _flash_fwd(cfg, q, k, v, qpos, kpos, scale)

    def fwd(q, k, v, qpos, kpos):
        out, lse = _flash_fwd(cfg, q, k, v, qpos, kpos, scale)
        return (out, lse), (q, k, v, qpos, kpos, out, lse)

    def bwd(res, cts):
        do, _ = cts                      # no cotangent flows into lse
        q, k, v, qpos, kpos, out, lse = res
        b, s, hq, hd = q.shape
        hkv, dv = k.shape[2], v.shape[-1]
        g = hq // hkv
        qc = _pick_chunk(s, cfg.attn_q_chunk)
        kc = _pick_chunk(s, cfg.attn_kv_chunk)
        nq, nk = s // qc, s // kc
        sc = hd ** -0.5 if scale is None else scale
        f32 = jnp.float32
        # chunk intermediates ride in the model dtype (bf16 on TPU: halves
        # the backward's HBM traffic; accumulation stays f32 via
        # preferred_element_type) — f32 inputs keep f32 for exact tests.
        wdt = q.dtype

        def grouped(x):                  # (B,S,Hq,w) -> (nq,B,Hkv,G,qc,w)
            w = x.shape[-1]
            xg = x.reshape(b, s, hkv, g, w).transpose(0, 2, 3, 1, 4)
            return xg.reshape(b, hkv, g, nq, qc, w).transpose(3, 0, 1, 2, 4, 5)

        q_chunks = grouped(q)
        do_chunks = grouped(do.astype(wdt))
        # delta_i = sum_d do * out per query (rescales dp -> ds)
        delta = jnp.sum(do.astype(f32) * out.astype(f32), axis=-1)  # (B,S,Hq)
        delta = delta.reshape(b, s, hkv, g).transpose(0, 2, 3, 1)
        delta_chunks = delta.reshape(b, hkv, g, nq, qc).transpose(3, 0, 1, 2, 4)
        lse_chunks = lse.reshape(b, hkv, g, nq, qc).transpose(3, 0, 1, 2, 4)
        qpos_chunks = qpos.reshape(b, nq, qc).transpose(1, 0, 2)

        kt = k.transpose(0, 2, 1, 3)                   # (B,Hkv,S,hd)
        vt = v.transpose(0, 2, 1, 3)
        k_chunks = kt.reshape(b, hkv, nk, kc, hd).transpose(2, 0, 1, 3, 4)
        v_chunks = vt.reshape(b, hkv, nk, kc, dv).transpose(2, 0, 1, 3, 4)
        kpos_chunks = kpos.reshape(b, nk, kc).transpose(1, 0, 2)

        def kv_step(dq_acc, kv_in):
            k_blk, v_blk, kpb = kv_in    # (B,Hkv,kc,hd), (B,kc)

            def q_step(carry, q_in):
                dk_blk, dv_blk = carry
                q_blk, do_blk, lse_blk, dl_blk, qpb = q_in
                sco = jnp.einsum("bngqd,bnkd->bngqk", q_blk, k_blk,
                                 preferred_element_type=f32) * sc
                sco = _softcap(sco, cfg.attn_logit_softcap)
                mask = _mask_block(cfg, qpb, kpb)[:, None, None]
                p = jnp.where(mask, jnp.exp(sco - lse_blk[..., None]), 0.0)
                p_w = p.astype(wdt)
                dv_blk = dv_blk + jnp.einsum("bngqk,bngqd->bnkd", p_w, do_blk,
                                             preferred_element_type=f32)
                dp = jnp.einsum("bngqd,bnkd->bngqk", do_blk, v_blk,
                                preferred_element_type=f32)
                ds = (p * (dp - dl_blk[..., None]) * sc).astype(wdt)
                dq_blk = jnp.einsum("bngqk,bnkd->bngqd", ds, k_blk,
                                    preferred_element_type=f32)
                dk_blk = dk_blk + jnp.einsum("bngqk,bngqd->bnkd", ds, q_blk,
                                             preferred_element_type=f32)
                return (dk_blk, dv_blk), dq_blk

            (dk_blk, dv_blk), dq_parts = jax.lax.scan(
                q_step, (jnp.zeros((b, hkv, kc, hd), f32),
                         jnp.zeros((b, hkv, kc, dv), f32)),
                (q_chunks, do_chunks, lse_chunks, delta_chunks, qpos_chunks),
            )
            return dq_acc + dq_parts, (dk_blk, dv_blk)

        dq0 = jnp.zeros((nq, b, hkv, g, qc, hd), f32)
        dq_chunks, (dk_chunks, dv_chunks) = jax.lax.scan(
            kv_step, dq0, (k_chunks, v_chunks, kpos_chunks)
        )
        dq = dq_chunks.transpose(1, 2, 3, 0, 4, 5).reshape(b, hkv, g, s, hd)
        dq = dq.transpose(0, 3, 1, 2, 4).reshape(b, s, hq, hd).astype(q.dtype)
        dk = dk_chunks.transpose(1, 2, 0, 3, 4).reshape(b, hkv, s, hd)
        dk = dk.transpose(0, 2, 1, 3).astype(k.dtype)
        dvs = dv_chunks.transpose(1, 2, 0, 3, 4).reshape(b, hkv, s, dv)
        dvs = dvs.transpose(0, 2, 1, 3).astype(v.dtype)
        return dq, dk, dvs, None, None

    flash.defvjp(fwd, bwd)
    return flash


def attention_forward(
    cfg: ModelConfig, params: dict, x: jnp.ndarray, positions: jnp.ndarray,
    compute_dtype,
) -> jnp.ndarray:
    """Training / prefill self-attention (no cache returned)."""
    q, k, v = _project_qkv(cfg, params, x, positions, compute_dtype)
    out = flash_attention(cfg, q, k, v, positions, positions,
                          softmax_scale(cfg))
    b, s = x.shape[:2]
    with jax.named_scope("attn.out"):
        return out.reshape(b, s, cfg.q_dim) @ params["w_o"].astype(compute_dtype)


def attention_prefill(
    cfg: ModelConfig, params: dict, x: jnp.ndarray, positions: jnp.ndarray,
    cache: dict, compute_dtype,
) -> tuple[jnp.ndarray, dict]:
    """Prefill: run causal attention AND write K/V into the cache at [0, S)."""
    q, k, v = _project_qkv(cfg, params, x, positions, compute_dtype)
    out = flash_attention(cfg, q, k, v, positions, positions,
                          softmax_scale(cfg))
    with jax.named_scope("attn.kv_write"):
        new_cache = {
            "k": jax.lax.dynamic_update_slice(
                cache["k"], k.astype(cache["k"].dtype), (0, 0, 0, 0)
            ),
            "v": jax.lax.dynamic_update_slice(
                cache["v"], v.astype(cache["v"].dtype), (0, 0, 0, 0)
            ),
        }
    b, s = x.shape[:2]
    with jax.named_scope("attn.out"):
        y = out.reshape(b, s, cfg.q_dim) @ params["w_o"].astype(compute_dtype)
    return y, new_cache


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, dtype) -> dict:
    shape = (batch, max_len, cfg.n_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def attention_decode(
    cfg: ModelConfig, params: dict, x: jnp.ndarray, pos: jnp.ndarray,
    cache: dict, compute_dtype, unit: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, dict]:
    """One-token decode: x (B, 1, d), pos (B,) current position.

    ``cache`` holds this layer's k/v (B, S, Hkv, hd), or with ``unit`` the
    stack over the layer scan's units (U, B, S, Hkv, hd) and this layer's
    index in it.  Writes the B new k/v rows at ``[unit,] b, pos[b]`` and
    attends over this layer's cache[0..pos]; the cache comes back with only
    those rows changed.  The attention core is jnp on every platform;
    the Pallas ``decode_attn`` kernel takes a (B, Hkv, S, hd) cache and is
    not on this path (calibration times it).
    """
    b = x.shape[0]
    positions = pos[:, None]                                   # (B, 1)
    q, k, v = _project_qkv(cfg, params, x, positions, compute_dtype)

    with jax.named_scope("attn.kv_write"):
        ck = _write_rows(cache["k"], k, pos, unit)
        cv = _write_rows(cache["v"], v, pos, unit)
    lk, lv = ck, cv
    if unit is not None:
        lk = jax.lax.dynamic_index_in_dim(ck, unit, keepdims=False)
        lv = jax.lax.dynamic_index_in_dim(cv, unit, keepdims=False)

    hkv, g = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads
    qg = q.reshape(b, hkv, g, cfg.head_dim)
    out = _decode_core(cfg, qg, lk, lv, pos, compute_dtype)
    with jax.named_scope("attn.out"):
        y = out.reshape(b, 1, cfg.q_dim).astype(compute_dtype) \
            @ params["w_o"].astype(compute_dtype)
    return y, {"k": ck, "v": cv}


def _write_rows(cache: jnp.ndarray, rows: jnp.ndarray, pos: jnp.ndarray,
                unit) -> jnp.ndarray:
    """Write row b of ``rows`` (B, 1, *row) at ``[unit,] b, pos[b]``: a row
    is (Hkv, hd) of K or V, or one latent-cache row.

    One dynamic-update-slice per sequence: it is done in place whatever
    layout the compiler gives the cache (a scatter asks for ``hd`` minor,
    and where the cache is kept with ``S`` minor the compiler copies the
    whole cache to meet it).
    """
    rows = rows.astype(cache.dtype)
    lead = () if unit is None else (unit,)
    for i in range(rows.shape[0]):
        row = rows[i].reshape((1,) * (cache.ndim - rows.ndim + 2)
                              + rows.shape[2:])
        cache = jax.lax.dynamic_update_slice(
            cache, row, (*lead, i, pos[i]) + (0,) * (rows.ndim - 2))
    return cache


@jax.named_scope("attn.core")
def _decode_core(cfg: ModelConfig, qg, ck, cv, pos, compute_dtype):
    """One query per sequence over its cache: (B, Hkv, G, hd) -> same."""
    s_max = ck.shape[1]
    kt = ck.astype(compute_dtype)
    vt = cv.astype(compute_dtype)
    sco = jnp.einsum("bngd,bsnd->bngs", qg, kt,
                     preferred_element_type=jnp.float32) * softmax_scale(cfg)
    sco = _softcap(sco, cfg.attn_logit_softcap)
    kv_pos = jnp.arange(s_max)[None, :]                        # (1, S)
    mask = kv_pos <= pos[:, None]
    if cfg.sliding_window > 0:
        mask = mask & ((pos[:, None] - kv_pos) < cfg.sliding_window)
    sco = jnp.where(mask[:, None, None, :], sco, NEG_INF)
    p = jax.nn.softmax(sco, axis=-1)
    return jnp.einsum("bngs,bsnd->bngd", p.astype(compute_dtype), vt,
                      preferred_element_type=jnp.float32)

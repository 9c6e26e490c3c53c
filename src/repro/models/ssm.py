"""Recurrent mixers: Mamba (selective SSM), Mamba-2 (state-space duality),
mLSTM and sLSTM (xLSTM).

All of them expose the same triplet of entry points:
  *_init(key, cfg, dtype)                  -> params
  *_forward(cfg, params, x, compute_dtype) -> y          (train/prefill)
  *_init_state / *_decode(...)             -> O(1) decode state + step

Mamba-1 and the xLSTM blocks process a sequence with ``lax.scan`` over
time — correct and HLO-compact; the per-step state is exactly the decode
state, so prefill and decode cannot drift apart.  Mamba-2 processes a
sequence by the chunked SSD algorithm (matrix products within a chunk, a
recurrence over chunk states) and decodes by the per-token recurrence,
which the chunks compute exactly.  These mixers carry no KV cache, which
is what makes ``long_500k`` decode feasible for jamba/xlstm.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .config import ModelConfig
from .layers import normal_init, out_proj_init, rmsnorm_init

F32 = jnp.float32


def _pin(x, par, *dims):
    """Sharding-constrain a scan input/carry so per-time-step ops stay
    local (without this, propagation can put an all-gather inside every
    step — measured 520k collectives per jamba train step).

    dims entries: "b" batch axes, "m" model axis, None replicated —
    divisibility-guarded.
    """
    if par is None or getattr(par, "mesh", None) is None:
        return x
    mesh = par.mesh
    n_data = 1
    for a in par.data_axes:
        n_data *= mesh.shape[a]
    baxes = par.data_axes if len(par.data_axes) > 1 else par.data_axes[0]
    spec = []
    for dim, want in zip(x.shape, dims):
        if want == "b" and n_data > 1 and dim % n_data == 0:
            spec.append(baxes)
        elif want == "m" and dim % mesh.shape[par.model_axis] == 0 \
                and mesh.shape[par.model_axis] > 1:
            spec.append(par.model_axis)
        else:
            spec.append(None)
    return jax.lax.with_sharding_constraint(x, P(*spec))


def _causal_conv(x, conv_state, w, b):
    """Causal depthwise convolution along S, continuing from the carried
    inputs, then SiLU.  x (B, S, C); conv_state (B, dc-1, C), the dc-1
    inputs before x; w (dc, C); b (C,).  Returns (silu(conv(x) + b),
    the last dc-1 inputs), in x's dtype."""
    s, dc = x.shape[1], w.shape[0]
    xpad = jnp.concatenate([conv_state.astype(x.dtype), x], axis=1)
    w = w.astype(x.dtype)
    y = sum(xpad[:, i : i + s, :] * w[i] for i in range(dc))
    y = jax.nn.silu(y + b.astype(x.dtype))
    return y, (xpad[:, s:, :] if dc > 1 else conv_state)


# ===================================================================== #
# Mamba (selective state-space, Mamba-1)
# ===================================================================== #


def mamba_init(key, cfg: ModelConfig, dtype) -> dict:
    d, di = cfg.d_model, cfg.d_inner
    n, r, dc = cfg.mamba_d_state, cfg.mamba_dt_rank, cfg.mamba_d_conv
    ks = jax.random.split(key, 6)
    # S4D-real initialization for A.
    a = jnp.tile(jnp.arange(1, n + 1, dtype=jnp.float32)[None, :], (di, 1))
    return {
        "w_in": normal_init(ks[0], (d, 2 * di), dtype),
        "conv_w": normal_init(ks[1], (dc, di), dtype, scale=0.1),
        "conv_b": jnp.zeros((di,), dtype),
        "w_x_proj": normal_init(ks[2], (di, r + 2 * n), dtype),
        "w_dt": normal_init(ks[3], (r, di), dtype, scale=r**-0.5),
        "dt_bias": jnp.log(jnp.expm1(jnp.full((di,), 0.01))).astype(jnp.float32),
        "a_log": jnp.log(a),
        "d_skip": jnp.ones((di,), jnp.float32),
        "w_out": out_proj_init(ks[4], (di, d), dtype, cfg.n_layers),
    }


def _mamba_scan_step(a_neg, carry, xt, dt, b_t, c_t):
    """One SSM step.  carry h: (B, di, N); xt/dt: (B, di); b/c: (B, N)."""
    da = jnp.exp(dt[..., None] * a_neg[None])                 # (B, di, N)
    dbx = dt[..., None] * b_t[:, None, :] * xt[..., None]     # (B, di, N)
    h = da * carry + dbx
    y = jnp.einsum("bdn,bn->bd", h, c_t)
    return h, y


def _mamba_inner(cfg, params, xz, conv_state, ssm_state, compute_dtype,
                 par=None):
    """Shared conv+SSM core.  xz: (B, S, 2*di).  States carried across calls.

    conv_state: (B, dc-1, di) trailing inputs; ssm_state: (B, di, N).
    Returns (y (B,S,di), new_conv_state, new_ssm_state).
    """
    n = cfg.mamba_d_state
    x, z = jnp.split(xz, 2, axis=-1)                          # (B, S, di)

    xc, new_conv_state = _causal_conv(x, conv_state, params["conv_w"],
                                      params["conv_b"])
    xc = _pin(xc, par, "b", None, "m")

    proj = xc @ params["w_x_proj"].astype(x.dtype)            # (B,S,r+2N)
    dt_r, b_ssm, c_ssm = jnp.split(
        proj, [cfg.mamba_dt_rank, cfg.mamba_dt_rank + n], axis=-1
    )
    # B/C/dt are tiny (N=16, r<=512): replicate them so the per-step scan
    # math is collective-free; di stays model-sharded.
    b_ssm = _pin(b_ssm, par, "b", None, None)
    c_ssm = _pin(c_ssm, par, "b", None, None)
    dt = jax.nn.softplus(
        (dt_r @ params["w_dt"].astype(x.dtype)).astype(jnp.float32)
        + params["dt_bias"]
    )                                                         # (B,S,di) fp32
    dt = _pin(dt, par, "b", None, "m")
    ssm_state = _pin(ssm_state, par, "b", "m", None)
    a_neg = -jnp.exp(params["a_log"])                         # (di, N)

    def step(h, inp):
        xt, dtt, bt, ct = inp
        h, y = _mamba_scan_step(a_neg, h, xt.astype(jnp.float32), dtt,
                                bt.astype(jnp.float32), ct.astype(jnp.float32))
        return h, y

    xs = (
        xc.transpose(1, 0, 2), dt.transpose(1, 0, 2),
        b_ssm.transpose(1, 0, 2), c_ssm.transpose(1, 0, 2),
    )
    h_last, ys = jax.lax.scan(step, ssm_state, xs)
    y = ys.transpose(1, 0, 2).astype(compute_dtype)           # (B,S,di)
    y = y + xc * params["d_skip"].astype(compute_dtype)
    y = y * jax.nn.silu(z)
    return y, new_conv_state.astype(jnp.float32), h_last


def mamba_forward(cfg, params, x, compute_dtype, par=None):
    b = x.shape[0]
    st = mamba_init_state(cfg, b)
    xz = x.astype(compute_dtype) @ params["w_in"].astype(compute_dtype)
    y, _, _ = _mamba_inner(cfg, params, xz, st["conv"], st["ssm"],
                           compute_dtype, par)
    return y @ params["w_out"].astype(compute_dtype)


def mamba_init_state(cfg: ModelConfig, batch: int) -> dict:
    return {
        "conv": jnp.zeros((batch, cfg.mamba_d_conv - 1, cfg.d_inner), jnp.float32),
        "ssm": jnp.zeros((batch, cfg.d_inner, cfg.mamba_d_state), jnp.float32),
    }


def mamba_decode(cfg, params, x, state, compute_dtype, par=None):
    """x: (B, 1, d) -> (y (B,1,d), new state)."""
    xz = x.astype(compute_dtype) @ params["w_in"].astype(compute_dtype)
    y, conv, ssm = _mamba_inner(cfg, params, xz, state["conv"], state["ssm"],
                                compute_dtype, par)
    return y @ params["w_out"].astype(compute_dtype), {"conv": conv, "ssm": ssm}


# ===================================================================== #
# Mamba-2 (state-space duality, arXiv:2405.21060)
# ===================================================================== #
#
# For a normed input u (the layout of the published checkpoints):
#   [z | xBC | dt] = u w_in;  xBC = silu(causal conv(xBC) + conv_b);
#   [x | B | C] = xBC;  dt = softplus(dt + dt_bias);  A = -exp(A_log);
#   per head h of group g:  S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t B_t^T,
#                           y_t = S_t C_t + D_h x_t;
#   out = rmsnorm(y * silu(z)) w_out.
# S is (head_dim, d_state) per head and stays float32, as does the
# convolution and everything between it and the gated norm.


def mamba2_init(key, cfg: ModelConfig, dtype) -> dict:
    """Mamba-2's published initialisation for the SSM's scalars: A uniform
    in [1, 16], dt log-uniform in [0.001, 0.1] (``dt_bias`` its inverse
    softplus), D = 1."""
    d, di, h = cfg.d_model, cfg.d_inner, cfg.mamba_n_heads
    cd = cfg.mamba_conv_dim
    ks = jax.random.split(key, 5)
    a = jax.random.uniform(ks[2], (h,), F32, 1.0, 16.0)
    dt = jnp.exp(jax.random.uniform(ks[3], (h,), F32, math.log(1e-3),
                                    math.log(0.1)))
    return {
        "w_in": normal_init(ks[0], (d, di + cd + h), dtype),
        "conv_w": normal_init(ks[1], (cfg.mamba_d_conv, cd), dtype, scale=0.1),
        "conv_b": jnp.zeros((cd,), dtype),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "A_log": jnp.log(a),
        "D": jnp.ones((h,), F32),
        "norm": rmsnorm_init(di, F32),
        "w_out": out_proj_init(ks[4], (di, d), dtype, cfg.n_layers),
    }


def mamba2_init_state(cfg: ModelConfig, batch: int) -> dict:
    """The last d_conv - 1 convolution inputs and the SSM state, f32."""
    return {
        "conv": jnp.zeros((batch, cfg.mamba_d_conv - 1, cfg.mamba_conv_dim),
                          F32),
        "ssm": jnp.zeros((batch, cfg.mamba_n_heads, cfg.mamba_head_dim,
                          cfg.mamba_d_state), F32),
    }


def _mamba2_in(cfg: ModelConfig, params, x, compute_dtype):
    """x (B, S, d) -> gate z (B, S, di), xBC (B, S, conv_dim) f32 and the
    time steps dt (B, S, H) f32 (softplus of the projection + dt_bias)."""
    di, cd = cfg.d_inner, cfg.mamba_conv_dim
    with jax.named_scope("ssm.proj"):
        zxbcdt = x.astype(compute_dtype) @ params["w_in"].astype(compute_dtype)
        z, xbc, dt = jnp.split(zxbcdt, [di, di + cd], axis=-1)
        dt = jax.nn.softplus(dt.astype(F32) + params["dt_bias"])
    return z, xbc.astype(F32), dt


def _split_xbc(cfg: ModelConfig, xbc):
    """(B, S, conv_dim) -> x (B, S, H, P), B and C (B, S, G, N)."""
    b, s = xbc.shape[:2]
    di, g, n = cfg.d_inner, cfg.mamba_n_groups, cfg.mamba_d_state
    x, bm, cm = jnp.split(xbc, [di, di + g * n], axis=-1)
    return (x.reshape(b, s, cfg.mamba_n_heads, cfg.mamba_head_dim),
            bm.reshape(b, s, g, n), cm.reshape(b, s, g, n))


def _mamba2_out(cfg: ModelConfig, params, y, z, compute_dtype):
    """Gated RMSNorm over d_inner, then the output projection: y (B, S,
    H, P) f32, z (B, S, di) -> (B, S, d)."""
    b, s = z.shape[:2]
    with jax.named_scope("ssm.out"):
        y = y.reshape(b, s, cfg.d_inner) * jax.nn.silu(z.astype(F32))
        y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                              + cfg.norm_eps) * params["norm"]["scale"]
        return y.astype(compute_dtype) @ params["w_out"].astype(compute_dtype)


def ssd(x, dt, a, bm, cm, chunk: int):
    """The SSM over a sequence by the chunked state-space-duality algorithm,
    in float32: within each chunk of ``chunk`` positions the outputs are
    matrix products over a causal decay mask, and one recurrence over the
    chunks carries the state from each chunk to the next.

    x (B, S, H, P), dt (B, S, H), a (H,) (negative), bm and cm (B, S, G,
    N), from a zero state.  Returns y (B, S, H, P) without the D skip, and
    the state (B, H, P, N) after the last position.  A length that is not
    a multiple of the chunk is padded with dt = 0, which neither decays the
    state nor adds to it."""
    b, s, h, p = x.shape
    g, n = bm.shape[2:]
    q = min(chunk, s)
    nc = -(-s // q)
    if nc * q > s:
        def pad(v):
            return jnp.pad(v, [(0, 0), (0, nc * q - s)] + [(0, 0)] * (v.ndim - 2))
        x, dt, bm, cm = pad(x), pad(dt), pad(bm), pad(cm)
    hg = h // g

    def chunks(v):                     # (B, nc q, ...) -> (nc, B, q, ...)
        return v.reshape(b, nc, q, *v.shape[2:]).swapaxes(0, 1)

    xdt = chunks((x * dt[..., None]).reshape(b, nc * q, g, hg, p))
    log_a = chunks((dt * a).reshape(b, nc * q, g, hg))
    causal = jnp.tril(jnp.ones((q, q), bool))[None, :, :, None, None]
    hi = jax.lax.Precision.HIGHEST

    def chunk_step(st, inp):
        # st (B, G, Hg, P, N); xc (B, q, G, Hg, P); la (B, q, G, Hg);
        # bc, cc (B, q, G, N)
        xc, la, bc, cc = inp
        cum = jnp.cumsum(la, axis=1)
        # decay from position s to l >= s of the chunk: exp(cum_l - cum_s)
        seg = cum[:, :, None] - cum[:, None]
        decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))  # (B, l, s, G, Hg)
        cb = jnp.einsum("blgn,bsgn->blsg", cc, bc, precision=hi)
        y = jnp.einsum("blsgh,bsghp->blghp", cb[..., None] * decay, xc,
                       precision=hi)
        y = y + jnp.einsum("blgn,bghpn->blghp", cc, st, precision=hi) \
            * jnp.exp(cum)[..., None]
        tail = jnp.exp(cum[:, -1:] - cum)[..., None]     # to the chunk's end
        st = st * jnp.exp(cum[:, -1])[..., None, None] + jnp.einsum(
            "bsghp,bsgn->bghpn", xc * tail, bc, precision=hi)
        return st, y

    st, ys = jax.lax.scan(chunk_step, jnp.zeros((b, g, hg, p, n), F32),
                          (xdt, log_a, chunks(bm), chunks(cm)))
    y = ys.swapaxes(0, 1).reshape(b, nc * q, h, p)[:, :s]
    return y, st.reshape(b, h, p, n)


def ssm_step(st, x, dt, a, bm, cm):
    """One position of the recurrence: st (B, H, P, N), x (B, H, P), dt
    (B, H), a (H,), bm and cm (B, G, N), f32.  Returns (S_t, S_t C_t)."""
    rep = st.shape[1] // bm.shape[1]
    bh = jnp.repeat(bm, rep, axis=1)[:, :, None, :]              # (B,H,1,N)
    ch = jnp.repeat(cm, rep, axis=1)[:, :, None, :]
    st = jnp.exp(dt * a)[..., None, None] * st \
        + (dt[..., None] * x)[..., None] * bh
    return st, jnp.sum(st * ch, axis=-1)


def mamba2_forward(cfg: ModelConfig, params: dict, x, compute_dtype):
    """Train and prefill: x (B, S, d), every sequence from position 0.
    Returns (y (B, S, d), the decode state after the last position)."""
    b = x.shape[0]
    z, xbc, dt = _mamba2_in(cfg, params, x, compute_dtype)
    st0 = mamba2_init_state(cfg, b)
    with jax.named_scope("ssm.conv"):
        xbc, conv = _causal_conv(xbc, st0["conv"], params["conv_w"],
                                 params["conv_b"])
    xh, bm, cm = _split_xbc(cfg, xbc)
    with jax.named_scope("ssm.ssd"):
        y, st = ssd(xh, dt, -jnp.exp(params["A_log"]), bm, cm,
                    cfg.mamba_chunk_size)
        y = y + params["D"][:, None] * xh
    return (_mamba2_out(cfg, params, y, z, compute_dtype),
            {"conv": conv, "ssm": st})


def mamba2_decode(cfg: ModelConfig, params: dict, x, state: dict,
                  compute_dtype, unit=None):
    """One-token decode: x (B, 1, d) -> (y (B, 1, d), new state).

    ``state`` holds this layer's conv and SSM state, or with ``unit`` the
    stacks over the layer scan's units and this layer's index in them:
    the layer's state is read from the stacks and the new state written
    back at ``unit``, so the stacks come back updated in place."""
    def read(a):
        return a if unit is None else jax.lax.dynamic_index_in_dim(
            a, unit, keepdims=False)

    def write(a, v):
        return v if unit is None else jax.lax.dynamic_update_index_in_dim(
            a, v, unit, 0)

    z, xbc, dt = _mamba2_in(cfg, params, x, compute_dtype)
    with jax.named_scope("ssm.conv"):
        xbc, conv = _causal_conv(xbc, read(state["conv"]), params["conv_w"],
                                 params["conv_b"])
        conv = write(state["conv"], conv)
    xh, bm, cm = _split_xbc(cfg, xbc)
    with jax.named_scope("ssm.state"):
        st, y = ssm_step(read(state["ssm"]), xh[:, 0], dt[:, 0],
                         -jnp.exp(params["A_log"]), bm[:, 0], cm[:, 0])
        y = (y + params["D"][:, None] * xh[:, 0])[:, None]
        st = write(state["ssm"], st)
    return (_mamba2_out(cfg, params, y, z, compute_dtype),
            {"conv": conv, "ssm": st})


# ===================================================================== #
# mLSTM (xLSTM matrix-memory block)
# ===================================================================== #


def mlstm_init(key, cfg: ModelConfig, dtype) -> dict:
    """Separate q/k/v projections so TP can shard the matrix memory by
    ROWS (v-index): C = f*C + i*(v k^T) and h = C q stay local per step
    when v/C-rows/h are model-sharded and q/k/n are replicated — zero
    collectives inside the time scan."""
    d, di = cfg.d_model, cfg.d_inner
    ks = jax.random.split(key, 6)
    return {
        "w_q_m": normal_init(ks[0], (d, di), dtype),
        "w_k_m": normal_init(ks[1], (d, di), dtype),
        "w_v_m": normal_init(ks[2], (d, di), dtype),
        "w_gates": normal_init(ks[3], (d, 2 * cfg.n_heads), jnp.float32),
        "b_gates": jnp.concatenate(
            [jnp.zeros((cfg.n_heads,)), jnp.full((cfg.n_heads,), 3.0)]
        ),  # forget-gate bias init high (remember by default)
        "w_z": normal_init(ks[4], (d, di), dtype),
        "w_out": out_proj_init(ks[5], (di, d), dtype, cfg.n_layers),
    }


def mlstm_init_state(cfg: ModelConfig, batch: int) -> dict:
    nh = cfg.n_heads
    dh = cfg.d_inner // nh
    return {
        "c": jnp.zeros((batch, nh, dh, dh), jnp.float32),
        "n": jnp.zeros((batch, nh, dh), jnp.float32),
        "m": jnp.full((batch, nh), -1e30, jnp.float32),
    }


def _mlstm_step(carry, inp):
    """Stabilized exponential-gating matrix-memory update."""
    c, n, m = carry
    q, k, v, log_i, log_f = inp        # q/k/v: (B,NH,dh); gates: (B,NH)
    m_new = jnp.maximum(log_f + m, log_i)
    i_g = jnp.exp(log_i - m_new)
    f_g = jnp.exp(log_f + m - m_new)
    c = f_g[..., None, None] * c + i_g[..., None, None] * (
        v[..., :, None] * k[..., None, :]
    )                                   # (B,NH,dh,dh) += v k^T  (row = v idx)
    n = f_g[..., None] * n + i_g[..., None] * k
    num = jnp.einsum("bhij,bhj->bhi", c, q)
    den = jnp.maximum(jnp.abs(jnp.einsum("bhj,bhj->bh", n, q)), 1.0)
    h = num / den[..., None]
    return (c, n, m_new), h


def _mlstm_core(cfg, params, x, state, compute_dtype, par=None):
    b, s, _ = x.shape
    nh = cfg.n_heads
    dh = cfg.d_inner // nh
    xq = x.astype(compute_dtype)
    q = xq @ params["w_q_m"].astype(compute_dtype)
    k = xq @ params["w_k_m"].astype(compute_dtype)
    v = xq @ params["w_v_m"].astype(compute_dtype)
    scale = dh ** -0.5
    q = q.reshape(b, s, nh, dh).astype(jnp.float32)
    k = (k.reshape(b, s, nh, dh) * scale).astype(jnp.float32)
    v = v.reshape(b, s, nh, dh).astype(jnp.float32)
    gates = xq.astype(jnp.float32) @ params["w_gates"] + params["b_gates"]
    log_i, f_raw = jnp.split(gates, 2, axis=-1)               # (B,S,NH)
    log_f = -jax.nn.softplus(-f_raw)                          # log sigmoid

    xs = tuple(a.transpose(1, 0, 2, 3) for a in (q, k, v)) + tuple(
        a.transpose(1, 0, 2) for a in (log_i, log_f)
    )
    carry0 = (state["c"], state["n"], state["m"])
    (c, n, m), hs = jax.lax.scan(_mlstm_step, carry0, xs)
    h = hs.transpose(1, 0, 2, 3).reshape(b, s, cfg.d_inner).astype(compute_dtype)
    z = jax.nn.silu(xq @ params["w_z"].astype(compute_dtype))
    y = (h * z) @ params["w_out"].astype(compute_dtype)
    return y, {"c": c, "n": n, "m": m}


def mlstm_forward(cfg, params, x, compute_dtype, par=None):
    y, _ = _mlstm_core(cfg, params, x, mlstm_init_state(cfg, x.shape[0]),
                       compute_dtype, par)
    return y


def mlstm_decode(cfg, params, x, state, compute_dtype, par=None):
    return _mlstm_core(cfg, params, x, state, compute_dtype, par)


# ===================================================================== #
# sLSTM (xLSTM scalar-memory block with per-head recurrence)
# ===================================================================== #


def slstm_init(key, cfg: ModelConfig, dtype) -> dict:
    d, di = cfg.d_model, cfg.d_inner
    nh = cfg.n_heads
    dh = di // nh
    ks = jax.random.split(key, 3)
    return {
        "w_x": normal_init(ks[0], (d, 4 * di), dtype),
        # block-diagonal recurrent weights, one (dh, 4*dh) block per head
        "r_h": normal_init(ks[1], (nh, dh, 4 * dh), jnp.float32, scale=dh**-0.5),
        "b": jnp.concatenate(
            [jnp.zeros((2 * di,)), jnp.full((di,), 3.0), jnp.zeros((di,))]
        ),  # (z, i, f, o) biases; forget bias high
        "w_out": out_proj_init(ks[2], (di, d), dtype, cfg.n_layers),
    }


def slstm_init_state(cfg: ModelConfig, batch: int) -> dict:
    nh = cfg.n_heads
    dh = cfg.d_inner // nh
    z = jnp.zeros((batch, nh, dh), jnp.float32)
    return {"c": z, "n": z + 1e-6, "h": z,
            "m": jnp.full((batch, nh, dh), -1e30, jnp.float32)}


def _slstm_step(params_rh, carry, x_gates):
    """x_gates: (B, 4*di) pre-activations from the input path."""
    c, n, h, m = carry                 # each (B, NH, dh)
    b = c.shape[0]
    nh, dh = c.shape[1], c.shape[2]
    rec = jnp.einsum("bhd,hdk->bhk", h, params_rh)            # (B,NH,4dh)
    pre = x_gates.reshape(b, nh, 4 * dh) + rec
    z_p, i_p, f_p, o_p = jnp.split(pre, 4, axis=-1)
    log_i = i_p
    log_f = -jax.nn.softplus(-f_p)     # log sigmoid
    m_new = jnp.maximum(log_f + m, log_i)
    i_g = jnp.exp(log_i - m_new)
    f_g = jnp.exp(log_f + m - m_new)
    z_v = jnp.tanh(z_p)
    c_new = f_g * c + i_g * z_v
    n_new = f_g * n + i_g
    h_new = jax.nn.sigmoid(o_p) * c_new / jnp.maximum(n_new, 1e-6)
    return (c_new, n_new, h_new, m_new), h_new


def _slstm_core(cfg, params, x, state, compute_dtype):
    b, s, _ = x.shape
    pre = (x.astype(compute_dtype) @ params["w_x"].astype(compute_dtype)
           ).astype(jnp.float32) + params["b"]
    xs = pre.transpose(1, 0, 2)        # (S, B, 4di)

    def step(carry, xg):
        return _slstm_step(params["r_h"], carry, xg)

    carry0 = (state["c"], state["n"], state["h"], state["m"])
    (c, n, h, m), hs = jax.lax.scan(step, carry0, xs)
    y = hs.transpose(1, 0, 2, 3).reshape(b, s, cfg.d_inner).astype(compute_dtype)
    y = y @ params["w_out"].astype(compute_dtype)
    return y, {"c": c, "n": n, "h": h, "m": m}


def slstm_forward(cfg, params, x, compute_dtype):
    y, _ = _slstm_core(cfg, params, x, slstm_init_state(cfg, x.shape[0]),
                       compute_dtype)
    return y


def slstm_decode(cfg, params, x, state, compute_dtype):
    return _slstm_core(cfg, params, x, state, compute_dtype)

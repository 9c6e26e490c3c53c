"""Smoke test of the main paths on a TPU, at LLaMA-MoE-3.5B widths.

    python chip_smoke.py            # one chip
    python chip_smoke.py --chips 4  # expert parallelism on four chips

One chip: serves LLaMA-MoE-3.5B (d_model 4096, 32 heads, 8 experts,
top-2, d_ff_expert 1376, vocab 32,000; random weights from a seed)
through ``repro.launch.serve.run``: batch 4, a 32-token prompt and 16
decode tokens, then the ``--traffic smoke`` fleet simulation with the
model's router counts.  Two checks follow:

- the logits of prefill and of each cached decode step agree with a
  teacher-forced full ``forward`` over the same tokens, both computed in
  f32 on the served weights (the served bf16 logits are reported);
- the fused ``FleetSim.run`` (Pallas deposit kernel on the chip) agrees
  with the ``FleetSim.run_legacy`` host anchor.

Four chips: the expert-parallel ``shard_map`` path on a (1, 4)
data x model mesh (2 experts per chip) against the same model
unsharded on one chip, for a forward pass (all-to-all dispatch) and
for cached decode steps (replicated tokens, psum combine), in f32.

Everything runs in this one process.  The script exits non-zero,
printing no result, when JAX finds no TPU or any check fails; its last
line is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "llama-moe-3.5b"
BATCH, PROMPT_LEN, DECODE_TOKENS = 4, 32, 16
#: Share of the chip's memory the weights plus the largest serving
#: program may take; the rest is left to the runtime, the KV cache, the
#: logits kept for the checks and the fleet simulation.
HBM_HEADROOM = 0.9
#: Depth of the four-chip comparison: the unsharded reference and the
#: sharded copy share chip 0, so the model is cut to one stage of a
#: two-stage layer pipeline.
EP_DEPTH = 16
EP_DECODE_STEPS = 4

# Logits tolerances, as relative L2 error per (request, position) over
# the vocabulary.  The gated comparisons compute in f32 with
# full-precision matmuls on the served bf16 weights: the two sides then
# differ only in f32 rounding order (single-query attention over the
# cache against chunked causal attention; sharded against unsharded
# matmuls and combines), which cannot flip a top-2 routing choice the
# way bf16 rounding does.
#: f32, median over positions.
F32_MEDIAN_TOL = 1e-4
#: f32, worst position.
F32_MAX_TOL = 1e-3
#: The context control (same decoded tokens after a different prompt)
#: must move the logits by more than this, or the check could not fail.
CONTROL_MIN_REL = 0.5
#: Fleet quantiles and per-request latencies: the tolerance of
#: tests/test_fleet_perf.py; served/shed/retry sets must be identical.
FLEET_RTOL = 1e-5


def _rel_err(got: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """Relative L2 error over the last axis."""
    return (np.linalg.norm(got - ref, axis=-1)
            / np.linalg.norm(ref, axis=-1))


def _logits_ok(name: str, got: np.ndarray, ref: np.ndarray,
               gate: bool = True) -> bool:
    """Print the relative-L2 spread of ``got`` against ``ref``; with
    ``gate``, hold it to the f32 tolerances."""
    rel = _rel_err(got, ref)
    top1 = float((got.argmax(-1) == ref.argmax(-1)).mean())
    ok = np.median(rel) <= F32_MEDIAN_TOL and rel.max() <= F32_MAX_TOL
    verdict = ((f"(<= {F32_MEDIAN_TOL}, {F32_MAX_TOL}) -> "
                f"{'PASS' if ok else 'FAIL'}") if gate else "(reported)")
    print(f"[check] {name}: rel-L2 median {np.median(rel):.3g} max "
          f"{rel.max():.3g}, top-1 agreement {top1:.3f} {verdict}")
    return bool(ok) or not gate


def _decode_logits(cfg, par, params, seq, prompt_len: int) -> np.ndarray:
    """Logits of prefill over ``seq[:, :prompt_len]`` and of one cached
    decode step per later token of ``seq``: (B, steps + 1, V)."""
    from repro.launch.steps import make_prefill_step, make_serve_step
    b, total = seq.shape
    logits, cache = jax.jit(make_prefill_step(cfg, par, total + 1))(
        params, {"tokens": seq[:, :prompt_len]})
    step = jax.jit(make_serve_step(cfg, par), donate_argnums=(1,))
    out = [logits]
    for t in range(prompt_len, total):
        _, logits, cache = step(params, cache, seq[:, t:t + 1],
                                jnp.full((b,), t, jnp.int32), None)
        out.append(logits)
    return np.stack([np.asarray(x, np.float32) for x in out], axis=1)


def _forward_logits(cfg, par, params, seq) -> np.ndarray:
    from repro.models import forward
    fwd = jax.jit(lambda p, t: forward(cfg, p, {"tokens": t}, par=par)[0])
    return np.asarray(fwd(params, seq), np.float32)


def _f32(cfg):
    return dataclasses.replace(cfg, compute_dtype="float32")


def _served_config(n_layers: int | None = None):
    """LLaMA-MoE-3.5B at published widths, dropless routing.

    With the default capacity factor (1.25) a 4-token decode step drops
    expert copies that a 192-token forward keeps, so the two sides of
    the logits check would route differently.  ``n_experts / top_k``
    gives every expert room for every token.
    """
    from repro.configs import get_config
    cfg = get_config(ARCH)
    return dataclasses.replace(
        cfg, n_layers=n_layers or cfg.n_layers,
        param_dtype=cfg.compute_dtype,
        capacity_factor=cfg.n_experts / cfg.top_k)


def _program_bytes(cfg) -> tuple[int, float]:
    """Device bytes of the weights plus the larger of prefill and the
    serve step, from the compiler's memory analysis, and the seconds
    their compilation took."""
    from repro.launch.steps import (cache_structs, make_prefill_step,
                                    make_serve_step, param_structs)
    from repro.models import Parallel
    par = Parallel()
    max_len = PROMPT_LEN + DECODE_TOKENS + 1
    params = param_structs(cfg)
    prompt = {"tokens": jax.ShapeDtypeStruct((BATCH, PROMPT_LEN), jnp.int32)}
    tok = jax.ShapeDtypeStruct((BATCH, 1), jnp.int32)
    pos = jax.ShapeDtypeStruct((BATCH,), jnp.int32)
    programs = [
        jax.jit(make_prefill_step(cfg, par, max_len)).lower(params, prompt),
        jax.jit(make_serve_step(cfg, par), donate_argnums=(1,)).lower(
            params, cache_structs(cfg, BATCH, max_len), tok, pos, None),
    ]
    need, t0 = 0, time.perf_counter()
    for lowered in programs:
        m = lowered.compile().memory_analysis()
        need = max(need, m.argument_size_in_bytes + m.output_size_in_bytes
                   - m.alias_size_in_bytes + m.temp_size_in_bytes)
    return need, time.perf_counter() - t0


def _hbm() -> tuple[int, int]:
    """(peak bytes in use, bytes limit) of the first device."""
    stats = jax.devices()[0].memory_stats()
    return stats["peak_bytes_in_use"], stats["bytes_limit"]


def choose_depth(cfg) -> int:
    """All layers if they fit with headroom, else half (one stage of a
    two-stage layer pipeline, the paper's ring of layer subnets)."""
    limit = _hbm()[1]
    for depth in (cfg.n_layers, cfg.n_layers // 2):
        need, compile_s = _program_bytes(
            dataclasses.replace(cfg, n_layers=depth))
        fits = need <= HBM_HEADROOM * limit
        print(f"[depth] {depth} layers need {need / 1e9:.2f} GB of "
              f"{limit / 1e9:.2f} GB -> {'fits' if fits else 'too big'} "
              f"(prefill + serve step compiled in {compile_s:.1f} s)")
        if fits:
            if depth < cfg.n_layers:
                print(f"[depth] CUT from {cfg.n_layers} to {depth} layers")
            return depth
    raise SystemExit(f"{ARCH} does not fit at {depth} layers")


def check_decode(cfg, out: dict) -> bool:
    """Prefill + cached decode logits == teacher-forced ``forward``.

    Gated in f32 on the served weights and tokens; the served bf16
    logits are reported against the bf16 forward and the f32 forward.
    """
    from repro.models import Parallel
    p, n, v = PROMPT_LEN, DECODE_TOKENS, cfg.vocab_size
    params, par = out["params"], Parallel()
    prompt = np.asarray(out["prompt"]["tokens"])
    seq = jnp.asarray(np.concatenate([prompt, out["generated"][:, :n]], 1))
    other = seq.at[:, :p].set(
        np.random.default_rng(123).integers(0, v, prompt.shape))
    with jax.default_matmul_precision("highest"):
        fwd32 = _forward_logits(_f32(cfg), par, params, seq)[:, p - 1:, :v]
        dec32 = _decode_logits(_f32(cfg), par, params, seq, p)[..., :v]
        ctrl = _forward_logits(_f32(cfg), par, params, other)[:, p:, :v]
    ok = _logits_ok("f32 decode vs f32 forward", dec32, fwd32)
    c = _rel_err(ctrl, fwd32[:, 1:])
    ctrl_ok = bool(c.min() > CONTROL_MIN_REL)
    print(f"[check] context control (other prompt): rel-L2 min "
          f"{c.min():.3f} (> {CONTROL_MIN_REL}) -> "
          f"{'PASS' if ctrl_ok else 'FAIL'}")
    dec16 = out["step_logits"][..., :v]
    fwd16 = _forward_logits(cfg, par, params, seq)[:, p - 1:, :v]
    _logits_ok("served bf16 decode vs bf16 forward", dec16, fwd16, False)
    _logits_ok("served bf16 decode vs f32 forward", dec16, fwd32, False)
    _logits_ok("bf16 forward vs f32 forward", fwd16, fwd32, False)
    return ok and ctrl_ok


def fleet_mismatches(fused, legacy, rtol: float = FLEET_RTOL) -> list[str]:
    """Differences between two TrafficResults beyond the parity contract
    of tests/test_fleet_perf.py (empty when they agree)."""
    bad = []
    for pf, pl in zip(fused.plans, legacy.plans):
        tag = pl.plan_name
        if not np.array_equal(pf.served, pl.served):
            bad.append(f"{tag}: served sets differ")
        if (pf.shed is None) != (pl.shed is None) or (
                pf.shed is not None and not (
                    np.array_equal(pf.shed, pl.shed)
                    and np.array_equal(pf.retries, pl.retries))):
            bad.append(f"{tag}: shed/retry sets differ")
        for which in ("ttft", "e2e", "tpot"):
            for q in (0.5, 0.99):
                a, b = pf.quantile(which, q), pl.quantile(which, q)
                if not ((np.isnan(a) and np.isnan(b))
                        or np.isclose(a, b, rtol=rtol)):
                    bad.append(f"{tag}: {which} p{q * 100:g} {a} vs {b}")
        for which in ("ttft_s", "e2e_s"):
            if not np.allclose(getattr(pf, which), getattr(pl, which),
                               rtol=rtol, equal_nan=True):
                bad.append(f"{tag}: per-request {which} beyond rtol")
        if pf.goodput_tok_s != pl.goodput_tok_s:
            bad.append(f"{tag}: goodput {pf.goodput_tok_s} vs "
                       f"{pl.goodput_tok_s}")
    return bad


def check_fleet(outcome) -> bool:
    """Fused on-chip ``FleetSim.run`` == ``run_legacy`` on the host."""
    sim, fused = outcome.sim, outcome.result
    mode = sim._deposit_mode()
    t0 = time.perf_counter()
    sim.run()                                    # warm: compiled already
    warm_s = time.perf_counter() - t0
    print(f"[fleet] {sim.n_requests} requests, {sim.n_rows} rows x "
          f"{sim.n_bins} bins, deposit {mode}; warm fused run "
          f"{warm_s:.3f} s on {jax.devices()[0].device_kind}")
    with jax.default_device(jax.devices("cpu")[0]):
        legacy = sim.run_legacy()
    bad = fleet_mismatches(fused, legacy)
    worst = np.nanmax([
        abs(pf.quantile(w, q) / pl.quantile(w, q) - 1.0)
        for pf, pl in zip(fused.plans, legacy.plans)
        for w in ("ttft", "e2e", "tpot") for q in (0.5, 0.99)])
    ok = mode == "pallas" and not bad
    print(f"[check] fused fleet vs legacy: served/shed identical "
          f"{not any('sets' in b for b in bad)}, worst quantile rel "
          f"{worst:.3g} (rtol {FLEET_RTOL}) -> {'PASS' if ok else 'FAIL'}")
    for b in bad:
        print(f"[check]   {b}")
    return ok


def smoke_one_chip() -> bool:
    from repro.launch import serve
    cfg = _served_config()
    cfg = dataclasses.replace(cfg, n_layers=choose_depth(cfg))
    print(f"[model] {ARCH}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"{cfg.n_heads} heads, {cfg.n_experts} experts top-{cfg.top_k}, "
          f"d_ff_expert {cfg.d_ff_expert}, vocab {cfg.vocab_size}, "
          f"{cfg.param_dtype} weights")
    args = serve.parse_args([
        "--batch", str(BATCH), "--prompt-len", str(PROMPT_LEN),
        "--decode-tokens", str(DECODE_TOKENS), "--traffic", "smoke"])
    out = serve.run(cfg, args)
    peak, limit = _hbm()
    print(f"[serve] depth {cfg.n_layers}, {out['tokens_per_s']:.2f} tok/s, "
          f"compile {out['compile_s']:.2f} s, peak memory "
          f"{peak / 1e9:.2f} GB of {limit / 1e9:.2f} GB")
    ok = check_decode(cfg, out)
    return check_fleet(out["fleet"]) and ok


def smoke_four_chips() -> bool:
    """Expert parallelism on a (1, 4) mesh against one unsharded chip,
    both computing in f32 on the same bf16 weights."""
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

    from repro.distributed.sharding import ShardingRules
    from repro.models import Parallel, forward, init_params, random_batch
    cfg = _served_config(EP_DEPTH)
    mesh = jax.make_mesh((1, 4), ("data", "model"),
                         axis_types=(AxisType.Auto, AxisType.Auto))
    par = Parallel(mesh=mesh)
    print(f"[ep] {cfg.name}: {cfg.n_layers} layers, {cfg.n_experts} experts "
          f"over {mesh.shape['model']} chips; MoE mode prefill "
          f"{par.resolve_moe(cfg, PROMPT_LEN)}, decode "
          f"{par.resolve_moe(cfg, 1)}")
    params = jax.jit(functools.partial(init_params, cfg))(
        jax.random.PRNGKey(0))
    rules = ShardingRules(cfg, mesh)
    params_ep = jax.device_put(params, jax.tree.map(
        lambda s: NamedSharding(mesh, s), rules.param_specs(params),
        is_leaf=lambda s: isinstance(s, P)))
    seq = random_batch(cfg, BATCH, PROMPT_LEN + EP_DECODE_STEPS,
                       seed=1)["tokens"]
    prompt, v, c32 = seq[:, :PROMPT_LEN], cfg.vocab_size, _f32(cfg)
    fwd_ep = jax.jit(lambda p, t: forward(c32, p, {"tokens": t}, par=par)[0])
    with jax.default_matmul_precision("highest"):
        n_a2a = fwd_ep.lower(params_ep, prompt).compile().as_text().count(
            " all-to-all(")
        print(f"[ep] sharded forward program: {n_a2a} all-to-all")
        got = {}
        for name, par_, p_ in (("ref", Parallel(), params),
                               ("ep", par, params_ep)):
            got[name] = (
                _forward_logits(c32, par_, p_, prompt)[..., :v],
                _decode_logits(c32, par_, p_, seq, PROMPT_LEN)[..., :v])
    ok = _logits_ok("EP forward vs one chip (f32)", got["ep"][0],
                    got["ref"][0])
    return _logits_ok("EP decode vs one chip (f32)", got["ep"][1],
                      got["ref"][1]) and ok and n_a2a > 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: serving + fleet on one chip; 4: expert "
                         "parallelism on a (1, 4) mesh vs one chip")
    args = ap.parse_args(argv)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    from repro.launch.cache import setup_compile_cache
    print(f"[setup] {len(devices)} x {devices[0].device_kind}, jax "
          f"{jax.__version__}, compile cache {setup_compile_cache()}")
    t0 = time.perf_counter()
    ok = smoke_four_chips() if args.chips == 4 else smoke_one_chip()
    print(f"[setup] wall {time.perf_counter() - t0:.1f} s")
    if not ok:
        print("chip_smoke: a check failed", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

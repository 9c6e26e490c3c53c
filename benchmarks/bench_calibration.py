"""Calibration validation: Eq. 43 predictions vs real measured decode steps.

The tentpole check of the service-time calibration layer
(``repro.core.calibration``): build a host-unit :class:`ServiceModel`
from freshly measured kernels, run a *real* sharded decode on a plan's
expert placement — the actual router picks the experts, each satellite's
expert group is the real FFN executed on real weights, the gateway step
is the real decode-attention kernel — and assert the engine's Eq. 43
per-layer latency predictions (same injected draws, zero-latency
topology) match the measured step times within :data:`TOLERANCE`.

Per validated config the measured per-layer step time is assembled from
really-executed phases, token by token:

    step(t) = t_attn(B=1) + max_s  t_ffn(visits of token t on satellite s)

i.e. the satellites run their routed visits in parallel (critical path =
slowest satellite), each satellite runs its own visits serially — exactly
the Eq. 43 contention semantic ``max_k q * t_expert`` the engine
computes.  The prediction side is ``evaluate_plans(...,
service_model=host_units)`` with the router's draws injected, so both
sides see the identical expert assignment and colocation pattern; the
expert service number crosses two independent code paths (the table
times the ``gmm_ref`` chain on (E, C, d) buckets, the decode executes
``models.moe.expert_ffn`` on per-satellite groups).

Tolerance is CPU-grade: single-core wall timings jitter, and XLA CPU
picks different dot kernels for the table's batched (E, C, d) buckets
than for a group's 2D matmuls (up to ~2x apart in achieved bandwidth at
these sizes), so the gate is a *factor* bound (measured/predicted
per-layer mean within [1/TOLERANCE, TOLERANCE]), not a percentage one.
Observed worst factor on the reference container is ~1.7 (a systematic
measured/predicted ~0.6 from exactly that kernel-choice gap).

Fails hard (SystemExit) on deviation — CI runs this as the calibration
regression gate and diffs the JSON against a committed baseline.

    PYTHONPATH=src python -m benchmarks.bench_calibration \
        --json-out BENCH_calibration.json
    PYTHONPATH=src python -m benchmarks.bench_calibration --refresh
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.core import (ActivationModel, ComputeConfig, MoEWorkload,
                        PlacementPlan, ServiceModel, TopologySample,
                        evaluate_plans)
from repro.core import calibration as cal

from .common import emit

#: Configs the real-decode validation runs on (>= 2 per the issue).
HARNESS_ARCHS = ("deepseek-moe-16b", "llama-moe-3.5b")

#: Archs whose satellite-unit tables are committed under
#: ``repro/core/calibration_tables/`` (``--refresh`` regenerates them).
COMMITTED_ARCHS = ("deepseek-moe-16b", "llama-moe-3.5b")

#: Measured/predicted per-layer mean must satisfy 1/TOL <= ratio <= TOL.
#: Factor bound, not a percentage: single-core CPU timings jitter by tens
#: of percent, and the harness intentionally crosses two code paths
#: (gmm_ref buckets for the table vs concatenated 2D chains for the
#: decode) whose XLA CPU kernels differ by up to ~2x in achieved
#: bandwidth.  Worst observed factor is ~1.7; 2.5 leaves CI headroom.
TOLERANCE = 2.5

#: Attention context of the harness decode (matches the harness table, so
#: the gateway prediction is the exact measured lookup).
CTX = 256

HARNESS_BATCHES = (1, 2, 4)
N_LAYERS = 2
N_EXPERT_SATS = 3          # experts spread over sats 1..3 => colocation, q>1


def _harness_config(arch: str):
    """Widened smoke config: same MoE family, dims big enough that one
    expert visit (~25 MB of weight reads, milliseconds) dwarfs the jit
    dispatch overhead (~0.3 ms on a single slow core) — at smoke dims a
    visit times at ~the call overhead and the factor comparison would be
    meaningless."""
    from repro.configs import smoke_config
    cfg = smoke_config(arch)
    return dataclasses.replace(
        cfg, d_model=1024, d_ff_expert=2048, n_experts=4,
        top_k=min(cfg.top_k, 4), n_shared_experts=0)


def _flat_topology(n_sats: int) -> TopologySample:
    """Fully-connected single-slot topology with ~zero hop latency, so
    the Eq. 43 comparison isolates the service terms."""
    edges = np.array([[i, j] for i in range(n_sats)
                      for j in range(i + 1, n_sats)], dtype=np.int64)
    return TopologySample(
        edges=edges,
        edge_mask=np.ones((1, len(edges)), dtype=bool),
        edge_latency=np.full((1, len(edges)), 1e-9),
        n_sats=n_sats,
    )


def _measure_real_decode(cfg, params, xs, draws, sat_of, iters: int):
    """Really execute the sharded decode, layer by layer, token by token.

    Returns (n_tokens, L) measured per-layer step seconds: the B=1
    decode-attention kernel plus the critical-path satellite FFN group.
    A satellite's group of v drawn experts runs as the concatenated 2D
    gated chain on the real weights —

        y = (silu(x @ Wg_cat) * (x @ Wu_cat)) @ Wd_cat

    with ``Wg_cat`` of shape (d, v*f) — mathematically the v expert FFNs
    on the shared token and the layout a sane serving runtime would pick.
    (The batched (v, 1, d) einsum formulation hits a pathological XLA CPU
    dot at v=1: ~50x slower than the identical 2D matmuls, which would
    measure the compiler's worst case rather than the satellite's work.)
    """
    import jax
    import jax.numpy as jnp

    from repro.kernels import ref
    from repro.kernels.ops import timed_call

    hkv, g_rep, hd = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads, \
        cfg.head_dim
    key = jax.random.PRNGKey(11)
    kq, kk = jax.random.split(key)
    q = jax.random.normal(kq, (1, hkv, g_rep, hd), jnp.float32)
    kv = jax.random.normal(kk, (1, hkv, CTX, hd), jnp.float32)
    pos = jnp.full((1,), CTX - 1, jnp.int32)
    t_attn = timed_call(jax.jit(ref.decode_attention_ref), q, kv, kv, pos,
                        iters=iters)

    d = cfg.d_model
    group = jax.jit(lambda x, wg, wu, wd:
                    (jax.nn.silu(x @ wg) * (x @ wu)) @ wd)

    n_tokens = draws.shape[1]
    out = np.zeros((n_tokens, N_LAYERS))
    for layer in range(N_LAYERS):
        p = params[layer]
        for t in range(n_tokens):
            groups: dict[int, list[int]] = {}
            for e in draws[layer, t]:
                groups.setdefault(int(sat_of[e]), []).append(int(e))
            x = xs[layer][t][None, :]                         # (1, d)
            t_exp = 0.0
            for elist in groups.values():
                sel = jnp.asarray(elist)
                wg = jnp.moveaxis(p["w_gate"][sel], 0, 1).reshape(d, -1)
                wu = jnp.moveaxis(p["w_up"][sel], 0, 1).reshape(d, -1)
                wd = p["w_down"][sel].reshape(-1, d)
                t_s = timed_call(group, x, wg, wu, wd, iters=iters)
                t_exp = max(t_exp, t_s)
            out[t, layer] = t_attn + t_exp
    return out


def validate_config(arch: str, n_tokens: int = 8, iters: int = 2) -> dict:
    """One config's measured-vs-predicted comparison; returns the record."""
    import jax
    import jax.numpy as jnp

    from repro.models.moe import moe_init, route

    cfg = _harness_config(arch)
    wl = MoEWorkload.from_model_config(cfg)
    compute = ComputeConfig()

    # Host-unit service model from a fresh measurement of this workload.
    # rows_per_expert=1 matches the B=1 decode semantic: every visit pays
    # its own weight read, same as the per-satellite groups below.
    measured = cal.measure_components(wl, CTX, HARNESS_BATCHES, impl="ref",
                                      iters=iters, rows_per_expert=1)
    table = cal.calibrate(f"{arch}-harness", wl, ctx_len=CTX,
                          batches=HARNESS_BATCHES, compute=compute,
                          measured=measured)
    svc = ServiceModel.calibrated(wl, compute, table, units="host")

    # Real MoE layers: real router picks the experts (= injected draws).
    key = jax.random.PRNGKey(7)
    keys = jax.random.split(key, 2 * N_LAYERS)
    params = [moe_init(keys[i], cfg, jnp.float32) for i in range(N_LAYERS)]
    xs = [jax.random.normal(keys[N_LAYERS + i], (n_tokens, cfg.d_model),
                            jnp.float32) for i in range(N_LAYERS)]
    draws = np.stack([
        np.asarray(route(cfg, params[i]["router"], xs[i])[1])
        for i in range(N_LAYERS)
    ])                                                   # (L, T, K)

    # Placement: gateway on sat 0, experts over sats 1..N_EXPERT_SATS —
    # colocation makes the Eq. 43 contention term q > 1 load-bearing.
    sat_of = 1 + np.arange(cfg.n_experts) % N_EXPERT_SATS
    plan = PlacementPlan(
        gateways=np.zeros(N_LAYERS, dtype=np.int64),
        expert_sats=np.tile(sat_of, (N_LAYERS, 1)),
        name=f"{arch}-harness",
    )
    topo = _flat_topology(1 + N_EXPERT_SATS)
    activ = ActivationModel.zipf(N_LAYERS, cfg.n_experts, cfg.top_k, seed=0)

    measured_tl = _measure_real_decode(cfg, params, xs, draws, sat_of, iters)
    res = evaluate_plans(
        [plan], topo, activ, wl, compute, np.random.default_rng(0),
        n_tokens=n_tokens, ctx_len=CTX, include_lm_head=False,
        slots=np.zeros(n_tokens, dtype=np.int64), draws=draws,
        service_model=svc,
    )[0]
    predicted_tl = res.layer_latency_s                   # (T, L)

    layers = []
    ok = True
    for layer in range(N_LAYERS):
        m = float(np.mean(measured_tl[:, layer]))
        p = float(np.mean(predicted_tl[:, layer]))
        ratio = m / p
        ok &= (1.0 / TOLERANCE) <= ratio <= TOLERANCE
        layers.append({"measured_s": m, "predicted_s": p,
                       "ratio": round(ratio, 4)})
    ratios = [ly["ratio"] for ly in layers]
    return {
        "config": arch,
        "n_tokens": n_tokens,
        "ctx_len": CTX,
        "tolerance": TOLERANCE,
        "table_hash": table.table_hash,
        "layers": layers,
        "worst_ratio": float(max(max(ratios), 1.0 / min(ratios))),
        "pass": bool(ok),
    }


def fleet_smoke() -> dict:
    """Calibrated FleetSim end-to-end smoke: one saturation point of the
    traffic world on the committed (or freshly built) llama-moe table."""
    from repro.traffic import FleetSim, get_scenario

    from .bench_traffic import _plans, _world

    con, topo, activ, wl, comp, ground = _world(True)
    try:
        table = cal.load_table("llama-moe-3.5b")
        source = "committed"
    except FileNotFoundError:
        table = cal.calibrate("llama-moe-3.5b", wl, ctx_len=CTX,
                              batches=HARNESS_BATCHES, compute=comp, iters=2)
        source = "fresh"
    svc = ServiceModel.calibrated(wl, comp, table)
    plans = _plans(con, topo, activ)[:1]
    sc = dataclasses.replace(get_scenario("smoke"), horizon_s=30.0,
                             tail_s=30.0, kv_slots=8)
    requests = sc.requests(np.random.default_rng(13), ground.n_stations,
                           rate_scale=2.0)
    slot_period = con.cfg.orbital_period_s / topo.n_slots
    sim = FleetSim(plans, topo, activ, wl, comp, requests,
                   np.random.default_rng(13),
                   qcfg=sc.queue_config(slot_period), ground=ground,
                   service_model=svc)
    res = sim.run_legacy()
    pl = res.plans[0]
    ttft = pl.quantile("ttft", 0.5)
    return {
        "table": source,
        "table_hash": table.table_hash,
        "plan": pl.plan_name,
        "ttft_p50_s": float(ttft),
        "goodput_tok_s": float(pl.goodput_tok_s),
        "finite": bool(np.isfinite(ttft)),
    }


def refresh_tables(ctx_len: int = 512, batches=(1, 2, 4, 8),
                   iters: int = 2) -> list[str]:
    """Regenerate the committed satellite-unit tables (full configs).

    ``rows_per_expert=2`` keeps the full-dim gmm chain tractable on a
    single CPU core; the derived satellite times depend on the measured
    *efficiency*, not the absolute bucket size.
    """
    from repro.configs import get_config

    compute = ComputeConfig()
    paths = []
    for arch in COMMITTED_ARCHS:
        wl = MoEWorkload.from_model_config(get_config(arch))
        measured = cal.measure_components(wl, ctx_len, tuple(batches),
                                          impl="ref", iters=iters,
                                          rows_per_expert=2)
        table = cal.calibrate(arch, wl, ctx_len=ctx_len,
                              batches=tuple(batches), compute=compute,
                              measured=measured)
        path = cal.save_table(table)
        paths.append(str(path))
        print(f"# wrote {path} (hash {table.table_hash})")
    return paths


def run(fast: bool = True, json_path: str | None = None) -> dict:
    """Validate every harness config + the fleet smoke; exits non-zero on
    any tolerance deviation (the CI calibration gate)."""
    n_tokens, iters = (8, 2) if fast else (16, 3)
    out: dict = {"tolerance": TOLERANCE, "configs": []}
    failed = []
    for arch in HARNESS_ARCHS:
        rec = validate_config(arch, n_tokens=n_tokens, iters=iters)
        out["configs"].append(rec)
        emit(f"calibration/{arch}",
             rec["layers"][0]["measured_s"] * 1e6,
             f"worst_ratio={rec['worst_ratio']:.3f};pass={rec['pass']}")
        if not rec["pass"]:
            failed.append(arch)
    out["fleet_calibrated"] = fleet_smoke()
    emit("calibration/fleet",
         out["fleet_calibrated"]["ttft_p50_s"] * 1e6,
         f"finite={out['fleet_calibrated']['finite']}")
    if not out["fleet_calibrated"]["finite"]:
        failed.append("fleet")
    out["pass"] = not failed
    out["_provenance"] = cal.provenance()

    if json_path:
        import json
        with open(json_path, "w") as f:
            json.dump(out, f, indent=2)
        print(f"# wrote {json_path}")
    if failed:
        raise SystemExit(
            f"bench_calibration: Eq. 43 predictions deviate beyond "
            f"{TOLERANCE}x on {failed} — recalibrate "
            f"(--refresh) or investigate the engine")
    return out


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--json-out", default=None, metavar="PATH")
    ap.add_argument("--refresh", action="store_true",
                    help="regenerate the committed satellite-unit tables")
    args = ap.parse_args()
    if args.refresh:
        refresh_tables()
        return
    run(fast=args.fast, json_path=args.json_out)


if __name__ == "__main__":
    main()

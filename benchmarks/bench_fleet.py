"""Fused fleet simulator: one-launch run_many sweep vs the legacy host loop.

The saturation-sweep workload of ``bench_traffic`` (smoke scenario at an
8x envelope rate, nested thinning masks) is executed twice on one shared
:class:`FleetSim` precompute:

* **legacy** — the pre-fusion per-fraction Python loop
  (``run_legacy`` per mask: host schedule/bin/gather, device scan, a
  (P, S, T) host<->device transfer per fixed-point iteration);
* **fused** — one ``run_many`` call: the whole sweep is a single compile
  + a single device launch of the fused fixed point, vmapped over the
  fraction axis.

The bench asserts fused<->legacy parity (identical served/shed sets,
goodput equal to 1e-9, TTFT/E2E quantiles within rtol 1e-5) and **fails
hard on deviation** — CI runs it as the fleet-path regression gate.  It
also reports per-stage legacy timings (schedule / bin / scan / gather)
so the JSON artifact tracks where the host loop spends its time, and a
before/after timing of the off-TPU deposit stage: the inline
``.at[].add`` scatter ("ref", the default off TPU) vs the row-bucketed
``segment_sum`` path (``deposit_impl="segments"``) over the sweep's
real compacted chunk triples — the measurement that keeps the segments
path opt-in.

    PYTHONPATH=src python -m benchmarks.run --fast --only fleet
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.traffic import FleetSim, get_scenario
from repro.traffic import queueing

from .bench_traffic import _plans, _world
from .common import Timer, emit

#: Thinning fractions of the envelope trace (the bench_traffic sweep).
FRACTIONS = np.array([0.1, 0.15, 0.2, 0.25, 0.3, 0.35, 0.4, 0.5,
                      0.6, 0.8, 1.0])


def _stage_times(sim: FleetSim, active: np.ndarray) -> dict:
    """Wall-time one legacy fixed-point pass, stage by stage."""
    P, M, L = sim.n_plans, sim.n_tokens, sim.n_layers
    z = np.zeros((P, M, L))
    with Timer() as t_sched:
        layer_arr, exp_arr, *_ = sim._schedule(z, z, sim.start_pref)
    with Timer() as t_bin:
        work = sim._bin_work(layer_arr, exp_arr,
                             np.broadcast_to(active[None, :],
                                             (P, sim.n_requests)))
    # No x64 scope: the legacy scan's inputs downcast to f32, exactly
    # as in run_legacy — time the kernel that actually runs.
    w = jnp.asarray(work)
    cap = jnp.asarray(sim.qcfg.buffer_s)
    jax.block_until_ready(
        queueing._fleet_queue_scan(w, cap, sim.qcfg.dt_s))      # compile
    with Timer() as t_scan:
        wait, dropped = queueing._fleet_queue_scan(w, cap, sim.qcfg.dt_s)
        jax.block_until_ready(wait)
    wait = np.asarray(wait)
    overload = np.asarray(dropped) > 0.0
    with Timer() as t_gather:
        sim._gather(wait, overload, layer_arr, exp_arr)
    return {
        "schedule_s": round(t_sched.seconds, 4),
        "bin_work_s": round(t_bin.seconds, 4),
        "scan_s": round(t_scan.seconds, 4),
        "gather_s": round(t_gather.seconds, 4),
    }


def _deposit_stage_times(sim: FleetSim, masks: np.ndarray) -> dict:
    """Before/after wall time of the fused deposit stage off TPU.

    Rebuilds the sweep's compacted chunk table exactly as ``_launch``
    does (the iteration-1 static bins), then times the inline
    scatter-add ("ref" — the off-TPU default) against the row-bucketed
    ``segment_sum`` path ("segments") on the identical COO triples.
    Both run under x64 like the fused launch itself.
    """
    from repro.kernels import ops as kernel_ops

    F = masks.shape[0]
    T, SR = sim.n_bins, sim.n_rows
    f_id, cid = np.nonzero(masks[:, sim._f_req])
    fprow = (f_id.astype(np.int32) * SR
             + sim._f_rowc[cid].astype(np.int32))
    bins = sim._f_bins0[cid]
    vals = sim._f_work[cid] * sim._f_fin0[cid]
    with jax.enable_x64():
        rows_d = jnp.asarray(fprow)
        bins_d = jnp.asarray(bins.astype(np.int64))
        vals_d = jnp.asarray(vals)
        flat = rows_d.astype(jnp.int64) * T + bins_d

        @jax.jit
        def ref_scat(fl, v):
            return jnp.zeros(F * SR * T).at[fl].add(
                v, mode="promise_in_bounds")

        def seg_scat(r, b, v):
            return kernel_ops.deposit_segments(r, b, v, F * SR, T)

        t_ref = kernel_ops.timed_call(ref_scat, flat, vals_d)
        t_seg = kernel_ops.timed_call(seg_scat, rows_d, bins_d, vals_d)
        parity = bool(np.array_equal(
            np.asarray(ref_scat(flat, vals_d)).reshape(F * SR, T),
            np.asarray(seg_scat(rows_d, bins_d, vals_d))))
    return {
        "n_chunks": int(cid.size),
        "n_rows": F * SR,
        "n_bins": T,
        "ref_s": round(t_ref, 4),
        "segments_s": round(t_seg, 4),
        "speedup": round(t_ref / max(t_seg, 1e-9), 2),
        "bitwise_ok": parity,
    }


def _check_parity(legacy: list, fused: list) -> list[str]:
    """Fused vs legacy per (fraction, plan): served/shed sets must be
    identical, goodput equal to 1e-9, latency quantiles within 1e-5."""
    problems = []
    for f, (rl, rf) in enumerate(zip(legacy, fused)):
        for pl_, pf in zip(rl.plans, rf.plans):
            tag = f"f={f} plan={pl_.plan_name}"
            if not np.array_equal(pl_.served, pf.served):
                problems.append(f"{tag}: served sets differ")
            if (pl_.shed is None) != (pf.shed is None) or (
                    pl_.shed is not None
                    and not np.array_equal(pl_.shed, pf.shed)):
                problems.append(f"{tag}: shed sets differ")
            if not np.isclose(pl_.goodput_tok_s, pf.goodput_tok_s,
                              rtol=1e-9, atol=1e-12):
                problems.append(f"{tag}: goodput {pl_.goodput_tok_s} vs "
                                f"{pf.goodput_tok_s}")
            for which in ("ttft", "e2e"):
                for q in (0.5, 0.99):
                    a, b = pl_.quantile(which, q), pf.quantile(which, q)
                    same = (np.isnan(a) and np.isnan(b)) or \
                        np.isclose(a, b, rtol=1e-5)
                    if not same:
                        problems.append(
                            f"{tag}: p{q:g} {which} {a} vs {b}")
    return problems


def run(fast: bool = True, json_path: str | None = None) -> dict:
    """Time the fused sweep against the legacy loop; emit BENCH_fleet rows.

    Returns the JSON-able summary (speedups, per-stage legacy timings,
    parity verdict).  Raises SystemExit when the fused/legacy parity
    check deviates, so CI smoke fails on fleet-path regressions.
    """
    con, topo, activ, wl, comp, ground = _world(fast)
    plans = _plans(con, topo, activ)[:2]
    sc = dataclasses.replace(get_scenario("smoke"),
                             horizon_s=60.0 if fast else 120.0,
                             tail_s=60.0, kv_slots=8)
    requests = sc.requests(np.random.default_rng(13), ground.n_stations,
                           rate_scale=8.0)
    slot_period = con.cfg.orbital_period_s / topo.n_slots
    with Timer() as t_build:
        sim = FleetSim(plans, topo, activ, wl, comp, requests,
                       np.random.default_rng(13),
                       qcfg=sc.queue_config(slot_period), ground=ground)
    u = np.random.default_rng(17).random(requests.n_requests)
    masks = u[None, :] < FRACTIONS[:, None]

    with Timer() as t_legacy:
        legacy = [sim.run_legacy(active=m) for m in masks]
    stages = _stage_times(sim, masks[-1])
    deposit_stage = _deposit_stage_times(sim, masks)
    with Timer() as t_first:             # compile + launch
        fused = sim.run_many(masks)
    with Timer() as t_steady:            # cached compile, one launch
        fused = sim.run_many(masks)

    problems = _check_parity(legacy, fused)
    if not deposit_stage["bitwise_ok"]:
        problems.append("deposit segments path deviates from ref scatter")
    speedup = t_legacy.seconds / max(t_steady.seconds, 1e-9)
    speedup_cold = t_legacy.seconds / max(t_first.seconds, 1e-9)
    out = {
        "fast": fast,
        "n_requests": requests.n_requests,
        "n_rates": len(FRACTIONS),
        "n_bins": sim.n_bins,
        "build_s": round(t_build.seconds, 3),
        "legacy_sweep_s": round(t_legacy.seconds, 3),
        "fused_first_s": round(t_first.seconds, 3),
        "fused_steady_s": round(t_steady.seconds, 3),
        "speedup_steady": round(speedup, 2),
        "speedup_with_compile": round(speedup_cold, 2),
        "legacy_stages": stages,
        "deposit_stage": deposit_stage,
        "parity_ok": not problems,
        "parity_problems": problems,
    }
    emit("fleet/legacy_sweep", t_legacy.seconds * 1e6,
         f"n_rates={len(FRACTIONS)}")
    emit("fleet/fused_sweep", t_steady.seconds * 1e6,
         f"speedup={speedup:.1f}x;with_compile={speedup_cold:.1f}x")
    print(f"# fused fleet sweep: {speedup:.1f}x over the legacy loop "
          f"({t_legacy.seconds:.2f}s -> {t_steady.seconds:.2f}s steady, "
          f"{t_first.seconds:.2f}s incl. compile); legacy stages {stages}")
    print(f"# deposit stage (off-TPU scatter relief): "
          f"ref {deposit_stage['ref_s']}s -> segments "
          f"{deposit_stage['segments_s']}s "
          f"({deposit_stage['speedup']}x, "
          f"bitwise_ok={deposit_stage['bitwise_ok']})")

    if json_path:
        import json
        with open(json_path, "w") as f:
            json.dump(out, f, indent=2)
    if problems:
        for p in problems:
            print(f"# PARITY DEVIATION: {p}")
        raise SystemExit("bench_fleet: fused/legacy parity check failed")
    return out


if __name__ == "__main__":
    run()

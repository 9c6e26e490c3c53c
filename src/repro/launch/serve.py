"""Serving driver: batched autoregressive decode with SpaceMoE placement.

The paper's kind is inference, so this is the headline end-to-end driver:

  1. calibrate: run a forward pass collecting per-layer expert-selection
     counts (the paper's activation statistics, Eq. 14 plug-in);
  2. plan: Theorem-1 expert->device placement per MoE layer on the EP
     ring (repro.core.device_placement), applied as a zero-cost weight
     permutation (repro.models.moe.apply_placement);
  3. serve: prefill a batch of prompts, decode N tokens per request with
     the jitted serve step; report tokens/s;
  4. account: expected dispatch-cost reduction vs identity placement, and
     the full space-network latency of the same token stream under the
     paper's constellation — SpaceMoE vs RandIntra-CG in one batched
     ``evaluate_plans`` sweep (``--traffic <scenario>`` upgrades this to
     the request-level fleet simulation of ``repro.traffic`` and prints
     the SLO table; ``--admission aimd --ttft-target T`` swaps the
     static KV cap for the latency-target admission controller with
     gateway retry);
  5. (optional) elastic: fail a device, re-plan, report migration bytes.

    PYTHONPATH=src python -m repro.launch.serve --arch llama-moe-3.5b \
        --smoke --batch 4 --prompt-len 32 --decode-tokens 16
    PYTHONPATH=src python -m repro.launch.serve --arch llama-moe-3.5b \
        --smoke --traffic smoke
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, smoke_config
from repro.core import (ActivationModel, ComputeConfig, Constellation,
                        ConstellationConfig, LinkConfig, MoEWorkload,
                        TorusSpec, evaluate_plans, expected_dispatch_cost,
                        identity_plan, plan_expert_devices,
                        rand_intra_cg_plan, sample_topology,
                        simulate_token_generation_legacy, spacemoe_plan)
from repro.distributed import migration, replan_on_failure
from repro.launch.cache import setup_compile_cache
from repro.launch.steps import make_prefill_step, make_serve_step
from repro.models import (ModelConfig, Parallel, forward, init_params,
                          random_batch)


def calibrate_router_stats(cfg, params, batch) -> np.ndarray | None:
    """(n_scan_units, E) expert-selection counts from one forward pass."""
    if not cfg.has_moe:
        return None
    fwd = jax.jit(functools.partial(forward, cfg, return_router_stats=True))
    _, _, counts = fwd(params, batch)
    return np.asarray(counts)


@functools.partial(jax.jit, static_argnums=(2,), donate_argnums=(0,))
def _permute_units(stack, perms, axis: int):
    """``stack[u] = take(stack[u], perms[u], axis)`` for every unit u.

    The loop rewrites one unit at a time into the donated stack, so the
    permutation never holds a second copy of the expert weights.
    """
    def body(u, s):
        unit = jax.lax.dynamic_index_in_dim(s, u, 0, keepdims=False)
        unit = jnp.take(unit, perms[u], axis=axis)
        return jax.lax.dynamic_update_index_in_dim(s, unit, u, 0)

    return jax.lax.fori_loop(0, stack.shape[0], body, stack)


def plan_and_apply_placement(cfg, params, counts: np.ndarray,
                             ep_ring: int = 16):
    """Per-unit Theorem-1 device placement, applied to the expert stacks.

    The router columns and the expert stacks of every MoE block are
    permuted in place (their buffers are donated): ``params`` must not
    be used afterwards.
    """
    e = cfg.n_experts
    ring = TorusSpec(shape=(min(ep_ring, e),), wrap=True)
    plans, costs = [], {"theorem1": 0.0, "identity": 0.0}
    perms = []
    for u in range(counts.shape[0]):
        w = counts[u] + 1e-3
        plan = plan_expert_devices(w, cfg.top_k, ring,
                                   bytes_per_token=2.0 * cfg.d_model)
        base = identity_plan(e, ring, bytes_per_token=2.0 * cfg.d_model)
        costs["theorem1"] += expected_dispatch_cost(plan, w, cfg.top_k)
        costs["identity"] += expected_dispatch_cost(base, w, cfg.top_k)
        plans.append(plan)
        perms.append(plan.expert_perm)
    perms = jnp.asarray(np.stack(perms), jnp.int32)     # (U, E)

    new_units = dict(params["units"])
    with warnings.catch_warnings():
        # CPU jit may decline the donation; the permutation is unchanged.
        warnings.filterwarnings("ignore", message=".*[Dd]onat")
        for bname, bparams in new_units.items():
            if isinstance(bparams, dict) and "ffn" in bparams \
                    and "router" in bparams["ffn"]:
                ffn = dict(bparams["ffn"])
                ffn["router"] = _permute_units(ffn["router"], perms, 1)
                for k in ("w_gate", "w_up", "w_down"):
                    ffn[k] = _permute_units(ffn[k], perms, 0)
                new_units[bname] = dict(bparams, ffn=ffn)
    params = dict(params, units=new_units)
    return params, plans, costs


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama-moe-3.5b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--decode-tokens", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--no-placement", action="store_true",
                    help="A/B: skip the Theorem-1 placement")
    ap.add_argument("--space-sim", action="store_true",
                    help="also simulate the constellation latency")
    ap.add_argument("--traffic", default=None, metavar="SCENARIO",
                    help="request-level fleet simulation under a named "
                         "repro.traffic scenario (implies --space-sim)")
    ap.add_argument("--admission", default=None,
                    choices=["static", "aimd", "pid"],
                    help="admission policy for --traffic: 'static' forces "
                         "the KV-slot cap (--kv-slots), 'aimd' switches to "
                         "the latency-target controller with gateway retry, "
                         "'pid' swaps in the PID cell on the same qhat "
                         "signal")
    ap.add_argument("--ttft-target", type=float, default=30.0,
                    help="TTFT target (s) the aimd admission controller "
                         "defends (with --admission aimd)")
    ap.add_argument("--kv-slots", type=int, default=8,
                    help="static KV-slot budget applied with "
                         "--admission static (0 = uncapped)")
    ap.add_argument("--replan", default=None,
                    choices=["off", "periodic", "backlog"],
                    help="continuous re-placement for --traffic: 'off' "
                         "holds the plans for the whole horizon, "
                         "'periodic' re-ranks the candidate pool every "
                         "topology slot, 'backlog' additionally inflates "
                         "scores with the live per-satellite backlog "
                         "(adds a replan/<mode> row to the table)")
    ap.add_argument("--ctrl", default="host", choices=["host", "fused"],
                    help="controller implementation for --replan "
                         "scenarios: 'host' walks the decide law round "
                         "by round, 'fused' runs the joint "
                         "replan+admission decide loop in one device "
                         "launch (same decisions; the exported trace "
                         "gains the joint decision-event channel)")
    ap.add_argument("--rate-scale", type=float, default=1.0,
                    help="multiply the --traffic scenario's arrival "
                         "rates (overload knob for admission/replan "
                         "demos and the CI trace smoke)")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="with --traffic: run the fleet simulation with "
                         "on-device probes and export the flight "
                         "recorder as Chrome/Perfetto trace-event JSON "
                         "(open at ui.perfetto.dev); also prints the "
                         "windowed fleet-telemetry table")
    ap.add_argument("--batching", type=int, default=0, metavar="B_MAX",
                    help="with --traffic: continuous decode batching in "
                         "the fleet queues — satellites drain decode "
                         "steps in batches of up to B_MAX per time bin "
                         "at the service model's batch rate (0 = off, "
                         "the bit-identical FIFO kernel)")
    ap.add_argument("--federation", type=int, default=0, metavar="K",
                    help="with --traffic: additionally serve the scenario "
                         "over a K-member constellation federation in one "
                         "fused launch; admission-shed requests overflow "
                         "to the next-best member (needs --admission "
                         "aimd/pid for overflow; reports the pooled "
                         "federation row plus one row per member)")
    ap.add_argument("--fail-device", type=int, default=-1,
                    help="elastic demo: fail this EP device and re-plan")
    return ap.parse_args(argv)


def main(argv=None) -> dict:
    args = parse_args(argv)
    setup_compile_cache()
    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    return run(cfg, args)


def run(cfg: ModelConfig, args: argparse.Namespace) -> dict:
    """Serve ``cfg`` under the parsed ``args`` (phases 1-5 above).

    Returns the printed results plus what a caller needs to check them:
    the served ``params``, the ``prompt`` and ``generated`` tokens, the
    ``step_logits`` (B, decode_tokens + 1, V) of prefill and each decode
    step, and with ``--traffic`` the scenario outcome (``fleet``).
    """
    # Inference keeps no master copy: weights live in the compute dtype.
    cfg = dataclasses.replace(cfg, param_dtype=cfg.compute_dtype)
    par = Parallel(mesh=None)
    params = jax.jit(functools.partial(init_params, cfg))(
        jax.random.PRNGKey(args.seed))
    out: dict = {"arch": cfg.name}

    # ---- 1-2: calibrate + place ---------------------------------------
    counts = None
    if cfg.has_moe:
        calib = random_batch(cfg, args.batch, args.prompt_len, seed=7)
        counts = calibrate_router_stats(cfg, params, calib)
        if not args.no_placement:
            params, plans, costs = plan_and_apply_placement(cfg, params, counts)
            red = (1 - costs["theorem1"] / costs["identity"]) * 100 \
                if costs["identity"] else 0.0
            out["dispatch_cost"] = costs
            print(f"[placement] expected dispatch cost: theorem1="
                  f"{costs['theorem1']*1e6:.1f}us identity="
                  f"{costs['identity']*1e6:.1f}us  (-{red:.1f}%)")
            if args.fail_device >= 0:
                w = counts.sum(axis=0) + 1e-3
                ring = TorusSpec(shape=(min(16, cfg.n_experts),), wrap=True)
                plan0 = plan_expert_devices(w, cfg.top_k, ring)
                plan1, survivors = replan_on_failure(
                    w, cfg.top_k, ring, {args.fail_device})
                bytes_per_expert = 3 * cfg.d_model * cfg.d_ff_expert * 2
                mig = migration(plan0, plan1, bytes_per_expert, survivors)
                out["migration_bytes"] = mig.bytes_moved
                print(f"[elastic] device {args.fail_device} failed: "
                      f"{len(mig.moved_experts)} experts move, "
                      f"{mig.bytes_moved/1e6:.1f} MB")

    # ---- 3: serve ------------------------------------------------------
    batch = random_batch(cfg, args.batch, args.prompt_len, seed=args.seed)
    prompt = {k: v for k, v in batch.items() if k != "labels"}
    max_len = args.prompt_len + args.decode_tokens + 1
    t0 = time.perf_counter()
    prefill_fn = jax.jit(make_prefill_step(cfg, par, max_len)) \
        .lower(params, prompt).compile()
    compile_s = time.perf_counter() - t0
    logits, cache = prefill_fn(params, prompt)
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    pos = jnp.full((args.batch,), args.prompt_len, jnp.int32)
    emb = (jnp.ones((args.batch, 1, cfg.d_model), jnp.float32)
           if cfg.frontend == "audio" else None)
    t0 = time.perf_counter()
    serve_step = jax.jit(make_serve_step(cfg, par), donate_argnums=(1,)) \
        .lower(params, cache, tok, pos, emb).compile()
    compile_s += time.perf_counter() - t0
    out["compile_s"] = compile_s
    generated = [np.asarray(tok)]
    step_logits = [logits]
    t0 = time.perf_counter()
    for _ in range(args.decode_tokens):
        tok, logits, cache = serve_step(params, cache, tok, pos, emb)
        pos = pos + 1
        generated.append(np.asarray(tok))
        step_logits.append(logits)
    jax.block_until_ready(logits)
    dt = time.perf_counter() - t0
    toks = args.batch * args.decode_tokens
    out["tokens_per_s"] = toks / dt
    out["params"] = params
    out["prompt"] = prompt
    out["generated"] = np.concatenate(generated, axis=1)
    out["step_logits"] = np.stack(
        [np.asarray(lg, np.float32) for lg in step_logits], axis=1)
    assert np.isfinite(out["step_logits"]).all()
    print(f"[serve] {toks} tokens in {dt:.2f}s -> {out['tokens_per_s']:.1f} tok/s "
          f"on {jax.devices()[0].device_kind} (compile {compile_s:.1f}s)")

    # ---- 4: space-network latency accounting ---------------------------
    if (args.space_sim or args.traffic) and cfg.has_moe:
        n_layers = counts.shape[0]
        # Each layer's subnet needs its own slot on a plane's ring
        # (N_y >= L), so deep models get longer planes.
        ccfg = ConstellationConfig.scaled(12, max(16, n_layers), n_slots=20)
        con = Constellation(ccfg)
        rng = np.random.default_rng(1)
        topo = sample_topology(con, LinkConfig(token_dim=cfg.d_model), rng)
        activ = ActivationModel.from_router_counts(counts, cfg.top_k)
        wl = MoEWorkload(
            d_model=cfg.d_model, n_heads=cfg.n_heads,
            n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
            d_ff_expert=cfg.d_ff_expert, n_experts=cfg.n_experts,
            top_k=cfg.top_k, vocab_size=cfg.vocab_size,
        )
        comp = ComputeConfig()
        sweep = [
            spacemoe_plan(con, topo, activ, wl, comp),
            rand_intra_cg_plan(ccfg, n_layers, cfg.n_experts,
                               np.random.default_rng(3)),
        ]
        # One batched sweep; both plans share the rng(2) token stream,
        # exactly what the legacy per-plan path consumed.
        sm, cg = evaluate_plans(sweep, topo, activ, wl, comp,
                                np.random.default_rng(2), n_tokens=200)
        if args.smoke:
            for plan, res in zip(sweep, (sm, cg)):
                ref = simulate_token_generation_legacy(
                    plan, topo, activ, wl, comp, np.random.default_rng(2),
                    n_tokens=200)
                assert abs(res.mean_s - ref.mean_s) / ref.mean_s < 1e-5, \
                    f"engine/legacy divergence for {plan.name}"
        out["space_latency_s"] = {"SpaceMoE": sm.mean_s,
                                  "RandIntra-CG": cg.mean_s}
        print(f"[space-sim] s/token: SpaceMoE={sm.mean_s:.3f} "
              f"RandIntra-CG={cg.mean_s:.3f} "
              f"({cg.mean_s/sm.mean_s:.2f}x reduction)")

        if args.traffic:
            from repro.traffic import (AdmissionConfig, ReplanConfig,
                                       build_ground_segment, format_table,
                                       get_scenario, run_scenario)
            sc = get_scenario(args.traffic)
            if args.replan is not None:
                # Re-placement needs slot boundaries inside the horizon;
                # keep the scenario's own period when it pins one.
                sc = dataclasses.replace(
                    sc,
                    replan=(None if args.replan == "off"
                            else ReplanConfig(mode=args.replan)),
                    slot_period_s=sc.slot_period_s or 60.0)
            if args.admission in ("aimd", "pid"):
                sc = dataclasses.replace(
                    sc, kv_slots=0,
                    admission=AdmissionConfig(
                        policy=args.admission,
                        ttft_target_s=args.ttft_target),
                    slo=dataclasses.replace(sc.slo,
                                            ttft_s=args.ttft_target))
            elif args.admission == "static":
                sc = dataclasses.replace(sc, admission=None,
                                         kv_slots=args.kv_slots)
            if args.smoke:
                horizon = min(sc.horizon_s, 60.0)
                sc = dataclasses.replace(
                    sc, horizon_s=horizon, tail_s=60.0,
                    failure_at_s=(horizon / 2.0
                                  if sc.failure_at_s is not None else None))
            ground = build_ground_segment(
                con, LinkConfig(token_dim=cfg.d_model),
                min_elevation_deg=10.0)
            sim_kwargs = {}
            fused_replan = args.ctrl == "fused" and sc.replan is not None
            if args.trace:
                if fused_replan:
                    # The control launch records no probe rings (the
                    # decide loop owns the device pass); the exported
                    # trace carries the request spans plus the joint
                    # decision-event channel instead.
                    print("[trace] fused controller: probe rings off, "
                          "joint decision channel on")
                else:
                    from repro.obs import ProbeConfig
                    sim_kwargs["probes"] = ProbeConfig()
            if args.batching > 0:
                from repro.traffic import BatchingConfig
                sim_kwargs["batching"] = BatchingConfig(b_max=args.batching)
            res = run_scenario(sc, sweep, topo, activ, wl, comp,
                               np.random.default_rng(4), ground=ground,
                               constellation=con,
                               rate_scale=args.rate_scale, ctrl=args.ctrl,
                               **sim_kwargs)
            out["fleet"] = res
            rows = res.result.table(sc.slo, scenario=sc.name)
            if res.post_failure is not None:
                rows += res.post_failure.table(
                    sc.slo, scenario=f"{sc.name}(post)")
            print(format_table(rows, prefix="[traffic] "))
            out["traffic"] = rows
            for tag, rep in (("replan", res.replan),
                             ("replan(post)", res.post_replan)):
                if rep is None:
                    continue
                print(f"[{tag}] {rep.schedule.name}: "
                      f"{rep.n_switches} switch(es), "
                      f"{rep.total_migration_bytes/1e6:.1f} MB migrated "
                      f"over {len(rep.decisions)} decision(s)")
                out[tag] = {"switches": rep.n_switches,
                            "migration_bytes": rep.total_migration_bytes}
            if args.federation > 0:
                from repro.traffic import FederationConfig, make_federation
                from repro.traffic import queueing as _queueing
                fed_sc = dataclasses.replace(sc, replan=None)
                fed = make_federation(
                    fed_sc, args.federation, ccfg, wl, comp,
                    np.random.default_rng(6),
                    fed_cfg=FederationConfig(
                        overflow=fed_sc.admission is not None),
                    rate_scale=args.rate_scale, n_layers=n_layers,
                    n_experts=cfg.n_experts, top_k=cfg.top_k)
                t_before = _queueing.FUSED_TRACE_COUNT
                fres = fed.run()
                frow = fres.federated.row(fed_sc.slo)
                frows = [{"scenario": f"{sc.name}(fed)", **frow}]
                for k, mem in enumerate(fres.members):
                    mrow = mem.plans[fed.serve_plan].row(fed_sc.slo)
                    mrow["plan"] = f"member{k}/{mrow['plan']}"
                    frows.append({"scenario": f"{sc.name}(fed)", **mrow})
                print(format_table(frows, prefix="[federation] "))
                print(f"[federation] K={args.federation} members, "
                      f"{fres.n_rounds} overflow round(s), "
                      f"{int((fres.hops > 0).sum())} request(s) "
                      f"re-routed, "
                      f"{_queueing.FUSED_TRACE_COUNT - t_before} "
                      f"trace(s)")
                out["federation"] = {
                    "rows": frows, "n_rounds": fres.n_rounds,
                    "n_rerouted": int((fres.hops > 0).sum()),
                }
            if args.trace:
                from repro.obs import (build_flight_log,
                                       summarize_timeseries, write_trace)
                log = build_flight_log(res.sim, res.result,
                                       replan=res.replan,
                                       scenario=sc.name)
                trace = write_trace(args.trace, log)
                tw = summarize_timeseries(res.sim.last_probes,
                                          plan=log.plan)
                if tw:
                    print(format_table(tw, prefix="[telemetry] "))
                print(f"[trace] {len(trace['traceEvents'])} events "
                      f"({len(log.requests)} requests, "
                      f"{len(log.events)} control instants) -> "
                      f"{args.trace}")
                out["trace"] = {"path": args.trace,
                                "n_events": len(trace["traceEvents"]),
                                "n_control_events": len(log.events)}
    return out


if __name__ == "__main__":
    main()

"""Fused fleet-simulator guards: compilation stability of run_many (a
whole rate sweep = exactly one trace of the fused kernel), fused<->legacy
parity on smoke and regional-hotspot scenarios (including the AIMD
admission regime), run vs run_many consistency, and Pallas deposit-kernel
parity with the scatter-add reference in interpret mode."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (ActivationModel, ComputeConfig, Constellation,
                        ConstellationConfig, LinkConfig, MoEWorkload,
                        rand_intra_cg_plan, sample_topology, spacemoe_plan)
from repro.traffic import (AdmissionConfig, FleetSim, QueueConfig,
                           build_ground_segment, get_scenario,
                           sample_requests)
from repro.traffic import queueing

CFG = ConstellationConfig.scaled(8, 12, n_slots=10, survival_prob=1.0)
WL = MoEWorkload.llama_moe_3p5b()
COMP = ComputeConfig()


def _world(seed=0, n_layers=4, n_experts=4, top_k=2):
    con = Constellation(CFG)
    topo = sample_topology(con, LinkConfig(), np.random.default_rng(seed))
    activ = ActivationModel.zipf(n_layers, n_experts, top_k, seed=1)
    ground = build_ground_segment(con, LinkConfig(), min_elevation_deg=10.0)
    plans = [spacemoe_plan(con, topo, activ),
             rand_intra_cg_plan(con.cfg, n_layers, n_experts,
                                np.random.default_rng(7))]
    return con, topo, activ, ground, plans


def _assert_parity(res_fused, res_legacy, rtol=1e-5):
    """Identical served/shed/retry sets; latency quantiles to rtol."""
    for pf, pl in zip(res_fused.plans, res_legacy.plans):
        np.testing.assert_array_equal(pf.served, pl.served)
        assert (pf.shed is None) == (pl.shed is None)
        if pf.shed is not None:
            np.testing.assert_array_equal(pf.shed, pl.shed)
            np.testing.assert_array_equal(pf.retries, pl.retries)
        for which in ("ttft", "e2e", "tpot"):
            for q in (0.5, 0.99):
                a, b = pf.quantile(which, q), pl.quantile(which, q)
                assert (np.isnan(a) and np.isnan(b)) \
                    or np.isclose(a, b, rtol=rtol), (which, q, a, b)
        np.testing.assert_allclose(pf.ttft_s, pl.ttft_s, rtol=rtol,
                                   equal_nan=True)
        np.testing.assert_allclose(pf.e2e_s, pl.e2e_s, rtol=rtol,
                                   equal_nan=True)
        assert pf.goodput_tok_s == pl.goodput_tok_s


# --------------------------------------------------------------------- #
# Fused <-> legacy parity
# --------------------------------------------------------------------- #


def test_fused_matches_legacy_smoke_with_kv_cap():
    """Smoke-style trace under the static KV cap: the fused single-launch
    fixed point must reproduce the host loop (served sets identical,
    quantiles within 1e-5)."""
    con, topo, activ, ground, plans = _world()
    req = sample_requests(np.random.default_rng(8), rate_rps=2.0,
                          horizon_s=40.0, n_stations=1, prompt_median=4,
                          prompt_max=16, decode_mean=4, decode_max=8)
    sim = FleetSim(plans, topo, activ, WL, COMP, req,
                   np.random.default_rng(5),
                   qcfg=QueueConfig(dt_s=0.05, tail_s=30.0, kv_slots=4))
    _assert_parity(sim.run(), sim.run_legacy())


def test_fused_matches_legacy_hotspot_admission():
    """Regional-hotspot overload under the AIMD controller with gateway
    retry: identical shed/retry resolution and latency parity."""
    con, topo, activ, ground, plans = _world()
    sc = dataclasses.replace(get_scenario("regional-hotspot"),
                             horizon_s=40.0)
    req = sc.requests(np.random.default_rng(9), ground.n_stations,
                      rate_scale=5.0)
    qcfg = QueueConfig(dt_s=0.05, tail_s=40.0,
                       admission=AdmissionConfig(ttft_target_s=15.0))
    sim = FleetSim(plans, topo, activ, WL, COMP, req,
                   np.random.default_rng(5), qcfg=qcfg, ground=ground)
    res_f, res_l = sim.run(), sim.run_legacy()
    assert any(p.shed_rate > 0 for p in res_f.plans)   # genuinely shedding
    _assert_parity(res_f, res_l)
    # The backlog observation the replan controller reads survives the
    # fused path's row compaction (expanded back to every satellite).
    assert sim.last_wait.shape == (len(plans), topo.n_sats, sim.n_bins)


def test_fused_matches_legacy_with_schedule_migration():
    """A switching PlanSchedule's migration background load is deposited
    identically by both paths."""
    from repro.core import PlanSchedule
    con, topo, activ, ground, plans = _world()
    sched = PlanSchedule(plans=plans,
                         slot_plan=np.array([0, 1] * 5), name="flip")
    req = sample_requests(np.random.default_rng(3), rate_rps=1.0,
                          horizon_s=60.0, n_stations=1, prompt_median=4,
                          prompt_max=16, decode_mean=4, decode_max=8)
    qcfg = QueueConfig(dt_s=0.05, tail_s=30.0, slot_period_s=20.0,
                       migration_bytes_per_expert=1e6)
    sim = FleetSim([sched], topo, activ, WL, COMP, req,
                   np.random.default_rng(5), qcfg=qcfg)
    assert sim._mig_work.size > 0            # migration load present
    _assert_parity(sim.run(), sim.run_legacy())


# --------------------------------------------------------------------- #
# Compilation stability
# --------------------------------------------------------------------- #


def test_run_many_sweep_is_one_trace_and_matches_run():
    """A 5-point rate sweep through run_many triggers exactly one trace
    of the fused kernel; a same-shape re-run triggers none; every sweep
    entry equals the corresponding single run()."""
    con, topo, activ, ground, plans = _world()
    req = sample_requests(np.random.default_rng(37), rate_rps=1.5,
                          horizon_s=37.0, n_stations=1, prompt_median=4,
                          prompt_max=16, decode_mean=4, decode_max=8)
    sim = FleetSim(plans, topo, activ, WL, COMP, req,
                   np.random.default_rng(5),
                   qcfg=QueueConfig(dt_s=0.05, tail_s=30.0))
    u = np.random.default_rng(1).random(req.n_requests)
    fractions = np.array([0.2, 0.4, 0.6, 0.8, 1.0])
    masks = u[None, :] < fractions[:, None]

    before = queueing.FUSED_TRACE_COUNT
    many = sim.run_many(masks)
    assert queueing.FUSED_TRACE_COUNT == before + 1
    sim.run_many(masks)                      # same shapes: cache hit
    assert queueing.FUSED_TRACE_COUNT == before + 1

    single = sim.run(active=masks[2])
    for pm, ps in zip(many[2].plans, single.plans):
        np.testing.assert_array_equal(pm.served, ps.served)
        np.testing.assert_allclose(pm.ttft_s, ps.ttft_s, rtol=1e-12,
                                   equal_nan=True)
        np.testing.assert_allclose(pm.e2e_s, ps.e2e_s, rtol=1e-12,
                                   equal_nan=True)


def test_run_many_target_axis_matches_per_target_runs():
    """The admission-frontier batching: run_many over TTFT targets equals
    per-target construction-time configs."""
    con, topo, activ, ground, plans = _world()
    sc = dataclasses.replace(get_scenario("regional-hotspot"),
                             horizon_s=30.0)
    req = sc.requests(np.random.default_rng(4), ground.n_stations,
                      rate_scale=4.0)
    targets = np.array([8.0, 30.0])

    def make(t):
        return FleetSim(plans[:1], topo, activ, WL, COMP, req,
                        np.random.default_rng(5),
                        qcfg=QueueConfig(
                            dt_s=0.05, tail_s=30.0,
                            admission=AdmissionConfig(ttft_target_s=t)),
                        ground=ground)

    batched = make(targets[0]).run_many(
        np.ones((2, req.n_requests), dtype=bool), ttft_targets=targets)
    for t, res in zip(targets, batched):
        _assert_parity(res, make(t).run())


# --------------------------------------------------------------------- #
# Pallas deposit kernel
# --------------------------------------------------------------------- #


def test_deposit_kernel_matches_ref_interpret():
    """Pallas one-hot-matmul deposit == jnp scatter-add oracle across
    paddings and duplicate targets (interpret mode on CPU; tolerance
    covers reduction-order freedom when duplicates collide in f32)."""
    from repro.kernels.ops import deposit
    from repro.kernels.ref import deposit_ref
    rng = np.random.default_rng(0)
    for n_rows, n_cols, n in [(17, 300, 1000), (144, 2568, 4096),
                              (8, 128, 7)]:
        rows = jnp.asarray(rng.integers(0, n_rows, n).astype(np.int32))
        cols = jnp.asarray(rng.integers(0, n_cols, n).astype(np.int32))
        vals = jnp.asarray(rng.random(n).astype(np.float32))
        out = deposit(rows, cols, vals, n_rows, n_cols, block_r=64,
                      block_c=256, block_t=128, interpret=True)
        ref = deposit_ref(rows, cols, vals, n_rows, n_cols)
        assert out.shape == (n_rows, n_cols)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-6, atol=1e-6)


def test_deposit_kernel_float64_interpret():
    """f64 deposits (the fused path's accumulation dtype) stay exact in
    interpret mode under scoped x64."""
    from repro.kernels.ops import deposit
    from repro.kernels.ref import deposit_ref
    rng = np.random.default_rng(1)
    with jax.enable_x64():
        rows = jnp.asarray(rng.integers(0, 11, 500).astype(np.int32))
        cols = jnp.asarray(rng.integers(0, 97, 500).astype(np.int32))
        vals = jnp.asarray(rng.random(500))
        out = deposit(rows, cols, vals, 11, 97, interpret=True)
        ref = deposit_ref(rows, cols, vals, 11, 97)
        assert out.dtype == jnp.float64
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-13, atol=1e-15)


def test_deposit_segments_bitwise_vs_ref():
    """The row-bucketed segment-sum deposit is BITWISE equal to the
    scatter-add oracle: the packed-key sort is stable (chunk index in
    the low bits), so per-(row, bin) f64 additions apply in table order,
    exactly like ``deposit_ref``.  Both the packed fast path and the
    ``bucketed=False`` plain segment_sum are pinned."""
    from repro.kernels.ops import deposit_segments
    from repro.kernels.ref import deposit_ref
    rng = np.random.default_rng(2)
    with jax.enable_x64():
        for n_rows, n_cols, n in [(17, 300, 1000), (144, 2568, 4096),
                                  (8, 128, 7), (3, 5, 0)]:
            rows = jnp.asarray(rng.integers(0, n_rows, n).astype(np.int32))
            cols = jnp.asarray(rng.integers(0, n_cols, n).astype(np.int32))
            vals = jnp.asarray(rng.standard_normal(n))
            ref = np.asarray(deposit_ref(rows, cols, vals, n_rows, n_cols))
            for bucketed in (True, False):
                out = deposit_segments(rows, cols, vals, n_rows, n_cols,
                                       bucketed=bucketed)
                assert out.dtype == jnp.float64
                np.testing.assert_array_equal(np.asarray(out), ref)
        # Row-grouped duplicates (the fleet chunk-table layout): many
        # chunks collide on one (row, bin) — order-sensitive in f64.
        rows = jnp.asarray(np.repeat(np.arange(7), 400).astype(np.int32))
        cols = jnp.asarray(rng.integers(0, 13, 2800).astype(np.int32))
        vals = jnp.asarray(rng.standard_normal(2800))
        np.testing.assert_array_equal(
            np.asarray(deposit_segments(rows, cols, vals, 7, 13)),
            np.asarray(deposit_ref(rows, cols, vals, 7, 13)))


def test_deposit_impl_segments_sim_bitwise():
    """``deposit_impl="segments"`` leaves the fused fleet results
    bit-identical to the default off-TPU scatter — served sets, TTFT and
    E2E traces all exact, so flipping the implementation never moves a
    trace."""
    con, topo, activ, ground, plans = _world()
    sc = dataclasses.replace(get_scenario("smoke"), horizon_s=30.0)
    req = sc.requests(np.random.default_rng(3), ground.n_stations)
    qcfg = QueueConfig(dt_s=0.05, tail_s=30.0)

    def run(impl):
        sim = FleetSim(plans, topo, activ, WL, COMP, req,
                       np.random.default_rng(5), qcfg=qcfg, ground=ground)
        sim.deposit_impl = impl
        return sim.run()

    a, b = run("ref"), run("segments")
    for pa, pb in zip(a.plans, b.plans):
        np.testing.assert_array_equal(pa.served, pb.served)
        np.testing.assert_array_equal(pa.ttft_s, pb.ttft_s)
        np.testing.assert_array_equal(pa.e2e_s, pb.e2e_s)
        np.testing.assert_array_equal(pa.token_total_s, pb.token_total_s)

"""Property tests pinning the continuous-batching invariants.

The deposit-time batching law (:mod:`repro.traffic.batching`) was chosen
precisely because its contracts are provable, so this layer pins them:

* **B_max = 1 is bitwise FIFO** — ``s == 1.0`` exactly makes the scaled
  plane an exact multiply-by-zero, at the law level and end-to-end
  through the fused kernel;
* **monotone in B_max** — a larger batch cap never makes any wait, any
  serve decision or the goodput worse (law-level pointwise, end-to-end
  at a congested operating point);
* **work conservation** — batching rescales *service* time, never the
  offered work: the raw offered-work accounting (``station_util``) is
  unchanged;
* **disposition conservation** — under AIMD admission + batching every
  offered request still lands in exactly one of served / shed /
  dropped;
* **static-flag parity** — ``batching=None`` traces the fused kernel
  exactly once and shares the batching-free compile-cache entry.

The law-level contracts run twice: always from a seeded numpy sampler
(tier-1 keeps coverage even without hypothesis installed), and fuzzed
under hypothesis when it is available (heavy example counts ride the
``slow`` nightly tier).  The end-to-end pins run the fast 8x12 world at
fixed seeds.
"""
import jax
import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAS_HYPOTHESIS = True
except ImportError:                                    # pragma: no cover
    HAS_HYPOTHESIS = False

from repro.core import (ActivationModel, ComputeConfig, Constellation,
                        ConstellationConfig, LinkConfig, MoEWorkload,
                        rand_intra_cg_plan, sample_topology, spacemoe_plan)
from repro.traffic import (AdmissionConfig, BatchingConfig, FleetSim,
                           QueueConfig, RequestBatch, build_ground_segment,
                           queueing)
from repro.traffic.batching import (batch_speedup_at, batched_effective_work,
                                    effective_work_np, windowed_counts,
                                    windowed_counts_jnp)

# --------------------------------------------------------------------- #
# Law-level contracts (checker functions shared by the seeded sampler
# and the hypothesis wrappers)
# --------------------------------------------------------------------- #


def check_table_contract(sp, b_max, kv):
    cfg = BatchingConfig(b_max=b_max, kv_slots_per_sat=kv,
                         speedup=tuple(sp))
    table = cfg.resolve_table()
    assert table.shape == (cfg.b_cap + 2,)
    assert table[0] == 1.0 and table[1] == 1.0      # s(1) = 1 exactly
    assert np.all(table >= 1.0)
    assert np.all(np.diff(table) >= 0.0)            # clamped monotone
    assert table[-1] == table[-2]                   # flat extension
    assert cfg.b_cap == (min(b_max, kv) if kv > 0 else b_max)


def check_law_contract(sp, b_max, b_hi, window, w, wd, c):
    cfg = BatchingConfig(b_max=b_max, speedup=tuple(sp))
    table = cfg.resolve_table()

    we, beff = effective_work_np(w, wd, c, table, cfg.b_cap, window)
    # Traced form agrees with the host form (window pre-applied); the
    # fused kernel always evaluates these planes under x64.
    with jax.enable_x64():
        we_j, beff_j = batched_effective_work(
            w, wd, np.asarray(windowed_counts_jnp(c, window)), table,
            float(cfg.b_cap))
    np.testing.assert_allclose(np.asarray(we_j), we, rtol=1e-12)
    np.testing.assert_allclose(np.asarray(beff_j), beff, rtol=1e-12)
    # B_eff stays in the admissible band; s >= 1 bounds the plane:
    # batching can only shrink work, and never below the prefill-only
    # residual (work conservation of the non-decode share).
    assert np.all(beff >= 1.0) and np.all(beff <= cfg.b_cap)
    assert np.all(we <= w + 1e-12)
    assert np.all(we >= (w - wd) - 1e-12)
    # Monotone in the cap: a larger B_max never increases any entry.
    t_hi = BatchingConfig(b_max=b_hi, speedup=tuple(sp)).resolve_table()
    we_hi, _ = effective_work_np(w, wd, c, t_hi, b_hi, window)
    assert np.all(we_hi <= we + 1e-12)


def check_bcap1_identity(sp, w, wd, c):
    cfg = BatchingConfig(b_max=1, speedup=tuple(sp))
    table = cfg.resolve_table()
    we, beff = effective_work_np(w, wd, c, table, cfg.b_cap)
    assert np.array_equal(we, w)                     # bitwise
    assert np.all(beff == 1.0)
    with jax.enable_x64():
        we_j, _ = batched_effective_work(w, wd, c, table, 1.0)
    assert np.array_equal(np.asarray(we_j), w)


def check_windowed_counts(cnt, window):
    c = np.asarray(cnt)
    out = windowed_counts(c, window)
    with jax.enable_x64():
        out_j = np.asarray(windowed_counts_jnp(c, window))
    np.testing.assert_allclose(out_j, out, rtol=1e-12)
    assert np.all(out >= c - 1e-12)                  # inclusive of own bin
    assert np.array_equal(windowed_counts(c, 1), c)  # window 1 = identity


def check_speedup_monotone(cnt, sp, b_max):
    c = np.sort(np.asarray(cnt))
    cfg = BatchingConfig(b_max=b_max, speedup=tuple(sp))
    s, beff = batch_speedup_at(c, cfg.resolve_table(), cfg.b_cap)
    assert np.all(np.diff(s) >= -1e-12)
    assert np.all(np.diff(beff) >= -1e-12)


def _sample_planes(rng, n):
    """(work, work_dec, cnt) arrays of length n with work_dec <= work."""
    w = rng.uniform(0.0, 50.0, n)
    wd = w * rng.uniform(0.0, 1.0, n)
    c = rng.uniform(0.0, 40.0, n)
    return w, wd, c


def test_law_contracts_seeded():
    """All law contracts over a seeded numpy sampler — the tier-1 path
    that needs no hypothesis install."""
    rng = np.random.default_rng(2024)
    for _ in range(60):
        n = int(rng.integers(1, 25))
        sp = rng.uniform(0.25, 16.0, int(rng.integers(1, 13)))
        b_max = int(rng.integers(1, 11))
        kv = int(rng.integers(0, 13))
        window = int(rng.integers(1, 5))
        w, wd, c = _sample_planes(rng, n)
        check_table_contract(sp, b_max, kv)
        check_law_contract(sp, b_max, b_max + int(rng.integers(0, 4)),
                           window, w, wd, c)
        check_bcap1_identity(sp, w, wd, c)
        check_windowed_counts(c, window)
        check_speedup_monotone(c, sp, b_max)


if HAS_HYPOTHESIS:
    def _floats(lo, hi):
        # XLA flushes subnormals to zero, so the traced forms cannot
        # match the host on them; work and counts are never that small.
        return st.floats(min_value=lo, max_value=hi, allow_nan=False,
                         allow_subnormal=False)

    speedups = st.lists(
        _floats(0.25, 16.0),
        min_size=1, max_size=12)
    counts = st.lists(
        _floats(0.0, 40.0),
        min_size=1, max_size=24)

    FAST = dict(max_examples=60, deadline=None)
    HEAVY = dict(max_examples=600, deadline=None)

    def _draw_planes(data, n):
        w = np.asarray(data.draw(st.lists(
            _floats(0.0, 50.0),
            min_size=n, max_size=n)))
        f = np.asarray(data.draw(st.lists(
            _floats(0.0, 1.0),
            min_size=n, max_size=n)))
        c = np.asarray(data.draw(st.lists(
            _floats(0.0, 40.0),
            min_size=n, max_size=n)))
        wd = w * f
        return w, np.where(wd < np.finfo(wd.dtype).tiny, 0.0, wd), c

    def _draw_law_case(data):
        sp = data.draw(speedups)
        b_max = data.draw(st.integers(min_value=1, max_value=10))
        b_hi = data.draw(st.integers(min_value=b_max, max_value=12))
        window = data.draw(st.integers(min_value=1, max_value=4))
        w, wd, c = _draw_planes(data, data.draw(
            st.integers(min_value=1, max_value=24)))
        return sp, b_max, b_hi, window, w, wd, c

    @given(sp=speedups, b_max=st.integers(min_value=1, max_value=12),
           kv=st.integers(min_value=0, max_value=12))
    @settings(**FAST)
    def test_resolve_table_contract(sp, b_max, kv):
        """Speedup tables are padded, clamped monotone, >= 1, s(1)=1."""
        check_table_contract(sp, b_max, kv)

    @given(data=st.data())
    @settings(**FAST)
    def test_batching_law_contract(data):
        """np/jnp agreement, B_eff band, work bounds, cap monotone."""
        check_law_contract(*_draw_law_case(data))

    @given(data=st.data())
    @settings(**FAST)
    def test_bcap1_is_bitwise_identity(data):
        """b_cap = 1 makes the law an exact no-op: work_eff == work
        bit-for-bit, whatever the speedup table said past entry 1."""
        sp = data.draw(speedups)
        w, wd, c = _draw_planes(data, data.draw(
            st.integers(min_value=1, max_value=24)))
        check_bcap1_identity(sp, w, wd, c)

    @given(cnt=counts, window=st.integers(min_value=1, max_value=6))
    @settings(**FAST)
    def test_windowed_counts_np_jnp_agree(cnt, window):
        """Host/traced window sums agree, are causal and inclusive."""
        check_windowed_counts(cnt, window)

    @given(cnt=counts, sp=speedups,
           b_max=st.integers(min_value=1, max_value=10))
    @settings(**FAST)
    def test_speedup_monotone_in_occupancy(cnt, sp, b_max):
        """s(B_eff) is non-decreasing in the occupancy count."""
        check_speedup_monotone(cnt, sp, b_max)

    @pytest.mark.slow
    @given(data=st.data())
    @settings(**HEAVY)
    def test_batching_law_contract_heavy(data):
        """Nightly: the law contract at heavy example counts."""
        check_law_contract(*_draw_law_case(data))

    @pytest.mark.slow
    @given(sp=speedups, b_max=st.integers(min_value=1, max_value=12),
           kv=st.integers(min_value=0, max_value=12))
    @settings(**HEAVY)
    def test_resolve_table_contract_heavy(sp, b_max, kv):
        """Nightly: the table contract at heavy example counts."""
        check_table_contract(sp, b_max, kv)


# --------------------------------------------------------------------- #
# End-to-end pins on the fast world
# --------------------------------------------------------------------- #

CFG = ConstellationConfig.scaled(8, 12, n_slots=10, survival_prob=1.0)
WL = MoEWorkload.llama_moe_3p5b()
COMP = ComputeConfig()


def _world(seed=0, n_layers=4, n_experts=4, top_k=2):
    con = Constellation(CFG)
    topo = sample_topology(con, LinkConfig(), np.random.default_rng(seed))
    activ = ActivationModel.zipf(n_layers, n_experts, top_k, seed=1)
    plans = [spacemoe_plan(con, topo, activ),
             rand_intra_cg_plan(con.cfg, n_layers, n_experts,
                                np.random.default_rng(7))]
    return con, topo, activ, plans


def _requests(n, gap_s, prompt=4, decode=12):
    return RequestBatch(
        arrival_s=np.arange(n, dtype=np.float64) * gap_s,
        prompt_len=np.full(n, prompt, dtype=np.int64),
        decode_len=np.full(n, decode, dtype=np.int64),
        station=np.zeros(n, dtype=np.int64),
    )


def _sim(topo, activ, plans, req, batching=None, admission=None,
         ground=None, tail_s=33.0):
    # tail_s=33 keeps this module's jit-cache entries distinct from
    # test_obs (31) / test_fleet_perf (30), so the FUSED_TRACE_COUNT
    # deltas below stay deterministic under a full suite run.
    return FleetSim(plans, topo, activ, WL, COMP, req,
                    np.random.default_rng(0),
                    qcfg=QueueConfig(dt_s=0.05, tail_s=tail_s,
                                     admission=admission),
                    ground=ground, batching=batching)


@pytest.fixture(scope="module")
def world():
    return _world()


def _assert_bitwise_equal(res_a, res_b):
    for pa, pb in zip(res_a.plans, res_b.plans):
        np.testing.assert_array_equal(pa.served, pb.served)
        for field in ("ttft_s", "e2e_s", "station_util"):
            np.testing.assert_array_equal(getattr(pa, field),
                                          getattr(pb, field))


def test_bmax1_bitwise_parity_fused(world):
    """B_max = 1 batching is bit-for-bit the FIFO fused kernel."""
    con, topo, activ, plans = world
    req = _requests(120, gap_s=1.0)
    res_fifo = _sim(topo, activ, plans, req).run()
    res_b1 = _sim(topo, activ, plans, req,
                  batching=BatchingConfig(b_max=1)).run()
    _assert_bitwise_equal(res_fifo, res_b1)


def test_kv_slot_bound_pins_fifo(world):
    """One KV slot per satellite caps the batch at 1 regardless of
    B_max: bitwise FIFO again (the occupancy bound, not the b_max pin)."""
    con, topo, activ, plans = world
    req = _requests(120, gap_s=1.0)
    res_fifo = _sim(topo, activ, plans, req).run()
    res_kv = _sim(topo, activ, plans, req,
                  batching=BatchingConfig(b_max=8,
                                          kv_slots_per_sat=1)).run()
    _assert_bitwise_equal(res_fifo, res_kv)


def test_goodput_monotone_in_bmax(world):
    """At a congested operating point, raising B_max never loses serves
    or goodput, and strictly gains somewhere along the sweep."""
    con, topo, activ, plans = world
    req = _requests(120, gap_s=0.6)
    served, goodput = [], []
    for b_max in (1, 2, 4, 8):
        res = _sim(topo, activ, plans, req,
                   batching=BatchingConfig(b_max=b_max)).run()
        served.append(sum(int(p.served.sum()) for p in res.plans))
        goodput.append(sum(p.goodput_tok_s for p in res.plans))
    assert served == sorted(served)
    assert all(b >= a - 1e-9 for a, b in zip(goodput, goodput[1:]))
    assert served[-1] > served[0]        # batching buys real capacity
    assert goodput[-1] > goodput[0]


def test_work_conservation_raw_offered(world):
    """Batching rescales service, never offered work: the raw
    offered-work accounting (station_util) matches FIFO exactly when
    both runs serve everything."""
    con, topo, activ, plans = world
    req = _requests(120, gap_s=1.0)
    res_fifo = _sim(topo, activ, plans, req).run()
    res_b = _sim(topo, activ, plans, req,
                 batching=BatchingConfig(b_max=8)).run()
    for pf, pb in zip(res_fifo.plans, res_b.plans):
        assert pf.served.all() and pb.served.all()
        np.testing.assert_allclose(pb.station_util, pf.station_util,
                                   rtol=1e-12)
        # ... while the experienced latency only improves.
        assert np.nanmean(pb.ttft_s) <= np.nanmean(pf.ttft_s) + 1e-12
        assert np.nanmean(pb.e2e_s) <= np.nanmean(pf.e2e_s) + 1e-12


def test_disposition_conservation_under_admission(world):
    """AIMD admission + batching: every offered request lands in exactly
    one of served / shed / dropped, retries only on served requests."""
    con, topo, activ, plans = world
    ground = build_ground_segment(con, LinkConfig(), min_elevation_deg=10.0)
    req = _requests(120, gap_s=0.6)
    res = _sim(topo, activ, plans, req,
               batching=BatchingConfig(b_max=8),
               admission=AdmissionConfig(ttft_target_s=2.0),
               ground=ground).run()
    for p in res.plans:
        n = p.n_active
        assert n == 120
        served, shed = p.served, p.shed
        assert shed is not None
        assert not np.any(served & shed)             # disjoint
        assert np.all(p.active[served]) and np.all(p.active[shed])
        dropped = p.active & ~served & ~shed
        assert int(served.sum() + shed.sum() + dropped.sum()) == n
        assert abs((1.0 - served.sum() / n) - p.shed_rate
                   - p.drop_rate) < 1e-12
        assert np.all(p.retries[~served] == 0)


def test_batching_off_trace_count_and_cache_share(world):
    """batching=None traces the fused kernel exactly once and shares
    the batching-free cache entry; a batched sim is its own entry."""
    con, topo, activ, plans = world
    req = _requests(60, gap_s=1.0)
    sim_a = _sim(topo, activ, plans, req, tail_s=34.0)
    sim_b = _sim(topo, activ, plans, req, tail_s=34.0)
    n0 = queueing.FUSED_TRACE_COUNT
    sim_a.run()
    assert queueing.FUSED_TRACE_COUNT - n0 == 1
    sim_b.run()                       # identical config: cached
    assert queueing.FUSED_TRACE_COUNT - n0 == 1
    sim_bat = _sim(topo, activ, plans, req, tail_s=34.0,
                   batching=BatchingConfig(b_max=8))
    sim_bat.run()                     # batched kernel: one more entry
    assert queueing.FUSED_TRACE_COUNT - n0 == 2
    sim_a.run()                       # plain kernel still cached
    assert queueing.FUSED_TRACE_COUNT - n0 == 2


@pytest.mark.slow
def test_goodput_monotone_in_bmax_dense(world):
    """Nightly: end-to-end near-monotonicity over a dense B_max grid.

    The law is pointwise monotone at fixed binning; end-to-end the
    fixed-point schedule re-bins deposits between runs, which can
    jitter a marginal request either way — allow that slack while
    pinning the capacity trend.
    """
    con, topo, activ, plans = world
    req = _requests(150, gap_s=0.5)
    served = []
    for b_max in (1, 2, 3, 4, 5, 6, 8, 12):
        res = _sim(topo, activ, plans, req,
                   batching=BatchingConfig(b_max=b_max)).run()
        served.append(sum(int(p.served.sum()) for p in res.plans))
    assert all(b >= a - 2 for a, b in zip(served, served[1:]))
    assert served[-1] > served[0]

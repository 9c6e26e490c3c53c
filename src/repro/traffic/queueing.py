"""Discrete-time per-satellite service model for request-level serving.

Every satellite of the constellation is a FIFO work queue (stations are
keyed by satellite id, S = V): a token deposits on the L gateway
satellites (attention + gating + lm-head service) and the per-layer
expert satellites (FFN service) of *the plan its topology slot selects*
— plans are time-indexed :class:`~repro.core.schedule.PlanSchedule`
entries, plain plans riding as constant schedules.  Colocated experts
share their satellite's queue (the queue-theoretic face of the Eq. 43
contention term), and a plan switch at a slot boundary redirects new
deposits while the old plan's backlog drains in place, with the moved
expert weights occupying destination queues as background load.  The
simulator is deliberately split into

1. a **base schedule** — per-token zero-load trajectories straight from
   the batched plan-evaluation engine (``core.engine.evaluate_plans``
   with wall-clock-derived slots and shared expert draws), so at zero
   load the traffic subsystem reproduces the engine exactly;
2. a **fleet queue kernel** — one ``lax.scan`` over time bins with the
   (plans, stations) backlog matrix as carry, vectorized over every
   plan of the sweep.  Backlogs are capped (finite buffers: overflow =
   backpressure drop) and each arrival's waiting time is the backlog it
   finds (exact for Poisson arrivals by PASTA, up to the O(dt) binning
   error the M/D/1 test bounds against Pollaczek-Khinchine);
3. a **closed-loop fixed point** — waits delay a token's delivery, and
   delivery times gate the autoregressive chain, so the schedule and
   the queue state are mutually dependent.  ``run`` iterates
   schedule -> bin -> scan -> gather a configurable number of times
   (``QueueConfig.iterations``): iteration 1 is the open-loop
   approximation, further iterations let congested tokens arrive
   *after* the backlog they caused has drained, which removes the
   open-loop bias of billing one backlog episode to every token of a
   request.  Deposits larger than one bin of service are spread over
   consecutive bins (chunked-prefill semantics, like production
   continuous-batching schedulers).

Two admission regimes guard KV-cache memory and the latency SLO:

* the legacy **static cap** — a request arriving when more than
  ``kv_slots`` requests are in flight is rejected (its offered load
  still occupies the queues: rejection happens at the ingress gateway
  *after* the uplink, the conservative accounting);
* the **latency-target controller** (``QueueConfig.admission`` with
  policy ``"aimd"``, see :mod:`repro.traffic.admission`) — an AIMD loop
  carried through the fleet scan observes the windowed critical-path
  backlog and sheds load *before* the target is crossed.  Rejections
  happen at the ground gateway before the uplink (shed load never
  enters the queues), and rejected requests retry at the next-best
  visible gateway with the retry latency accounted in TTFT/E2E.

``FleetSim`` precomputes everything rate-independent once (engine pass,
station indices, chunk layout) so a saturation sweep replays only the
binning + scan + gather per tested rate — no Python loop over requests
or tokens anywhere on the hot path.

Two execution paths share that precompute:

* the **fused device path** (``run`` / ``run_many``) — the whole
  schedule -> bin -> scan -> gather fixed point is one jitted
  ``lax.fori_loop`` (:func:`_fused_core`): the dense work tensor is
  built on device by a scatter-add deposit (:mod:`repro.kernels.deposit`:
  the one-hot-matmul kernel on TPU, the jnp reference scatter elsewhere,
  with a bitwise-identical row-bucketed ``segment_sum`` variant behind
  ``deposit_impl="segments"``),
  lives time-major, and never crosses the host boundary between
  iterations.  ``run_many`` vmaps the same core over a
  thinning-fraction (or admission-target) axis, so an entire saturation
  sweep is one compile + one launch.  The core is module-level and
  takes every per-simulator tensor as an argument, so fleet runs with
  equal shapes — every ``run_many`` rate, every re-placement
  decide/evaluate round — reuse one compile cache entry.  Dtype policy
  mirrors the host path exactly: schedules/bins/deposits in float64
  (``jax.enable_x64`` scoped to these launches), the
  backlog scan in float32 — the downcast ``run_legacy``'s jitted scans
  have always applied — so the two paths agree to the last bit in
  practice;
* the **legacy host path** (``run_legacy``) — the original NumPy
  fixed-point loop, kept verbatim as the authoritative semantic anchor.
  ``tests/test_fleet_perf.py`` pins fused<->legacy parity on identical
  served/shed sets and rtol <= 1e-5 latency quantiles.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (ScheduleBatch, evaluate_schedules,
                        schedule_ingress_offsets)
from repro.obs.probes import (DecisionTrace, ProbeConfig, ProbeRecord,
                              make_buffers)
from repro.kernels import ops as _kernel_ops
from repro.core.activation import ActivationModel
from repro.core.calibration import resolve_service_model
from repro.core.latency import ComputeConfig, TopologySample
from repro.core.schedule import (PlanSchedule, as_schedule,
                                 migration_matrix, slot_of_time)
from repro.core.workload import MoEWorkload

from .admission import (_PID_WINDUP, AdmissionConfig, admission_queue_scan,
                        control_bin_flags, resolve_admission)
from .batching import (BatchingConfig, batch_speedup_at,
                       batched_effective_work, effective_work_np,
                       windowed_counts, windowed_counts_jnp)
from .ground import GroundSegment
from .metrics import PlanTraffic, TrafficResult
from .requests import RequestBatch


@dataclasses.dataclass(frozen=True)
class QueueConfig:
    """Discrete-time queueing parameters.

    Attributes:
        dt_s: Time-bin width.  Per-visit service times below dt never
            self-queue; the binning error is O(dt).
        buffer_s: Per-station backlog cap in seconds of work; arrivals
            overflowing it are dropped (backpressure).
        kv_slots: Max requests concurrently holding KV cache (0 = no
            admission cap).  Ignored when the adaptive controller is
            active — the controller *replaces* the static cap.
        slot_period_s: Wall-clock seconds per topology slot (ties tokens
            to the constellation's time-varying graph; default is a
            550 km LEO period split over 20 slots).
        tail_s: Extra horizon past the last zero-load completion so
            in-flight requests can drain.  Congestion-stretched
            schedules beyond it clip into the final bin (such runs are
            deep in SLO failure anyway).
        iterations: Schedule<->queue fixed-point iterations (1 = open
            loop).
        admission: Optional :class:`~repro.traffic.admission
            .AdmissionConfig`; policy ``"aimd"`` switches the run loop
            to the latency-target controller with gateway retry.
        migration_bytes_per_expert: Weight bytes one expert drags to a
            new satellite when a :class:`~repro.core.schedule
            .PlanSchedule` switches plans at a slot boundary.
        migration_rate_gbps: ISL share available to weight migration;
            each moved expert occupies its destination satellite's queue
            for ``bytes * 8 / rate`` seconds of background load.
    """

    dt_s: float = 0.05
    buffer_s: float = 10.0
    kv_slots: int = 0
    slot_period_s: float = 300.0
    tail_s: float = 120.0
    iterations: int = 3
    admission: AdmissionConfig | None = None
    migration_bytes_per_expert: float = 1e6
    migration_rate_gbps: float = 10.0


# --------------------------------------------------------------------- #
# The fleet queue kernel
# --------------------------------------------------------------------- #


@jax.jit
def _fleet_queue_scan(work, cap, dt):
    """Scan the (P, S) backlog matrix over T time bins.

    work: (P, S, T) seconds of work arriving per bin.
    cap:  scalar or (S,) backlog cap in seconds.
    Returns (wait, dropped), both (P, S, T): ``wait[..., t]`` is the
    backlog an arrival in bin t finds (work deposited in bin t is seen
    by later bins only); ``dropped`` is the overflow discarded per bin.
    """
    def _step(backlog, w_t):
        wait = backlog
        total = backlog + w_t
        dropped = jnp.maximum(total - cap, 0.0)
        backlog = jnp.maximum(jnp.minimum(total, cap) - dt, 0.0)
        return backlog, (wait, dropped)

    p, s, _ = work.shape
    backlog0 = jnp.zeros((p, s), dtype=work.dtype)
    _, (wait, dropped) = jax.lax.scan(_step, backlog0,
                                      jnp.moveaxis(work, 2, 0))
    return jnp.moveaxis(wait, 0, 2), jnp.moveaxis(dropped, 0, 2)


def station_waiting_times(
    arrival_s: np.ndarray,
    service_s: np.ndarray | float,
    dt_s: float,
    buffer_s: float = np.inf,
    horizon_s: float | None = None,
    batching: BatchingConfig | None = None,
) -> np.ndarray:
    """Per-arrival waiting times at one FIFO station via the fleet kernel.

    Runs the same discrete-time scan the fleet simulator uses (P=1, S=1)
    and refines the bin-resolution backlog with the exact within-bin
    Lindley correction: an arrival at offset ``delta`` into bin b waits

        max(0, backlog_at_bin_start + work_of_earlier_same_bin_arrivals
               - delta),

    since the server drains continuously through the bin.  This is the
    single-station reference the M/D/1 Pollaczek-Khinchine test checks.

    Args:
        arrival_s: (n,) sorted arrival times, seconds.
        service_s: Scalar or (n,) per-arrival service demand, seconds.
        dt_s: Time-bin width of the underlying scan.
        buffer_s: Backlog cap (overflow is dropped), default unbounded.
        horizon_s: Optional simulation horizon (defaults to the last
            arrival).
        batching: Optional :class:`~repro.traffic.batching
            .BatchingConfig` — applies the continuous-batching law
            (deposit-time work scaling by the windowed-occupancy
            speedup; see :mod:`repro.traffic.batching`) to this
            station, arrivals counting one occupancy unit each.
            ``None`` is the exact FIFO reference.

    Returns:
        (n,) waiting time each arrival experiences before service.
    """
    t = np.asarray(arrival_s, dtype=np.float64)
    if len(t) and not (np.diff(t) >= 0).all():
        raise ValueError("arrivals must be sorted")
    s = np.broadcast_to(np.asarray(service_s, dtype=np.float64), t.shape)
    horizon = (float(t[-1]) if len(t) else 0.0) \
        if horizon_s is None else horizon_s
    n_bins = int(np.floor(horizon / dt_s)) + 2
    bins = np.minimum((t / dt_s).astype(np.int64), n_bins - 1)

    work = np.bincount(bins, weights=s, minlength=n_bins)
    sp_bin = np.ones(n_bins)
    if batching is not None:
        cnt = np.bincount(bins, minlength=n_bins).astype(np.float64)
        table = batching.resolve_table()
        work, _ = effective_work_np(
            work, work, cnt, table, batching.b_cap,
            batching.window_bins(dt_s))
        sp_bin, _ = batch_speedup_at(
            windowed_counts(cnt, batching.window_bins(dt_s)),
            table, batching.b_cap)
    wait_bins = np.asarray(
        _fleet_queue_scan(jnp.asarray(work[None, None, :]),
                          jnp.asarray(buffer_s), dt_s)[0])[0, 0]

    # Within-bin FIFO: prior work of same-bin arrivals (scaled by the
    # bin's batching speedup when enabled), minus the time already
    # elapsed inside the bin.
    cs = np.cumsum(s)
    first = np.searchsorted(bins, bins, side="left")
    prior = ((cs - s) - (cs[first] - s[first])) / sp_bin[bins]
    delta = t - bins * dt_s
    return np.maximum(wait_bins[bins] + prior - delta, 0.0)


# --------------------------------------------------------------------- #
# Helpers
# --------------------------------------------------------------------- #


def _exclusive_cumsum(a: np.ndarray, axis: int) -> np.ndarray:
    out = np.cumsum(a, axis=axis)
    return out - a


def _segment_any(flags: np.ndarray, seg_ids: np.ndarray,
                 n_seg: int) -> np.ndarray:
    """OR-reduce boolean ``flags`` (P, E) over segments of the last axis."""
    p, _ = flags.shape
    idx = np.arange(p)[:, None] * n_seg + seg_ids[None, :]
    hits = np.bincount(idx.ravel(), weights=flags.ravel().astype(np.float64),
                       minlength=p * n_seg)
    return hits.reshape(p, n_seg) > 0.0


def _station_quantile(values: np.ndarray, ok: np.ndarray,
                      station: np.ndarray, n_stations: int,
                      q: float) -> np.ndarray:
    """(P, G) per-(plan, station) q-quantile of ``values`` (P, R) over
    the requests with ``ok`` set; stations with no valid request fall
    back to the plan-wide quantile (0 when nothing is valid at all)."""
    p = values.shape[0]
    out = np.zeros((p, n_stations))
    overall = np.array([
        np.quantile(values[i][ok[i]], q) if ok[i].any() else 0.0
        for i in range(p)])
    for g in range(n_stations):
        sel = ok & (station[None, :] == g)
        for i in range(p):
            out[i, g] = np.quantile(values[i][sel[i]], q) if sel[i].any() \
                else overall[i]
    return out


# --------------------------------------------------------------------- #
# The fused device fixed point
# --------------------------------------------------------------------- #

#: Incremented once per trace of :func:`_fused_core` — the compilation
#: counter ``tests/test_fleet_perf.py`` pins (a whole rate sweep through
#: ``run_many`` must cost exactly one trace).
FUSED_TRACE_COUNT = 0

#: The compacted chunk table is padded to a multiple of this, so sweeps
#: with similar activity reuse the fused kernel's compile cache.
_CHUNK_BLOCK = 8192


def _fleet_fixed_point(consts, chunks, work0, work0_sum, ttft_target,
                       tpot_target, pbuf, batch, n_iter, n_bins, n_rows,
                       adm_on, deposit_mode, want_wait, probes,
                       batch_window):
    """Single-launch fleet fixed point (the device half of ``FleetSim.run``).

    Rolls the legacy schedule -> bin -> scan -> gather iteration into one
    ``lax.fori_loop`` over device-resident precomputes, batched over an
    explicit sweep axis F, so the dense work tensor never crosses the
    host boundary between iterations.  Pure module-level function: every
    per-simulator tensor arrives via ``consts`` (the pytree built by
    :meth:`FleetSim._device_tables`), so fleet runs with equal shapes
    share one jit cache entry.

    Two compactions keep the device arrays proportional to *offered*
    work rather than to the constellation:

    * **row compaction** — queue rows are the (plan, satellite) pairs
      that can ever receive a deposit, observation or gather
      (``FleetSim._build_row_map``), not all P x V pairs; zero-work
      stations contribute exactly zero in both paths, so dropping them
      is exact;
    * **chunk compaction** — ``chunks`` holds only the (sweep entry,
      chunk) pairs whose request is active (built host-side per launch
      from the masks, padded to a stable block size), so a thinned rate
      sweep deposits only what it offers.

    Layout/dtype policy (pinned by the parity tests): schedules, bins
    and deposits compute in float64 exactly like the host path; the work
    tensor lives **time-major** ``(T, F, rows)`` so the scan consumes it
    with no transposes; the backlog scan itself runs in float32 — the
    same downcast the legacy path's jitted scans have always applied —
    and emits *only* the wait trace (overload flags are recovered at the
    gather points from ``wait + work > cap``, bit-identical to the
    legacy ``dropped > 0``).

    The first fixed-point iteration is **peeled**: its schedule is the
    zero-wait schedule, known at construction, so its offered-work plane
    ``work0`` arrives as a launch input (one host ``np.bincount`` over
    the compacted chunks — not a per-iteration transfer) and the device
    spends its scatter budget only on the congestion-corrected
    iterations 2..n.

    Args:
        consts: Device-resident precompute pytree (see
            :meth:`FleetSim._device_tables` for the keys).
        chunks: Compacted deposit table — ``src`` (gather index into the
            F-flattened [layer_arr | exp_arr] pair), ``offs`` (chunk
            offset in bins), ``work`` (seconds), ``fprow`` (target row
            in the (F * rows) plane), and under admission ``fpr`` (index
            into the (F, P, R) shed mask).  Entries are grouped by row
            (static sort), so the scatter walks the plane row-major.
        work0: (F, rows, T) float32 iteration-1 offered work (migration
            background load already added).
        work0_sum: (F, rows) float64 per-row sum of iteration-1 work
            (utilization reporting when ``n_iter == 1``).
        ttft_target: (F,) margin-scaled TTFT targets (admission only).
        tpot_target: (F,) margin-scaled TPOT targets (admission only).
        n_iter: Static — schedule<->queue fixed-point iterations.
        n_bins: Static — T, the time-bin count.
        n_rows: Static — compacted queue-row count.
        adm_on: Static — run the AIMD admission regime.
        deposit_mode: Static — ``"pallas"`` (the one-hot-matmul TPU
            kernel; f32 accumulation), ``"segments"`` (row-bucketed
            sorted ``segment_sum`` — the non-TPU scatter relief, bitwise
            identical to the reference) or ``"ref"`` (the inline jnp
            scatter-add).
        want_wait: Static — carry and return the final backlog trace
            (the re-placement controller's observation).
        pbuf: Probe ring buffers (:func:`repro.obs.probes.make_buffers`
            pytree; donated by the probed jit wrapper) — an empty dict
            when ``probes`` is None.
        batch: Continuous-batching pytree — an **empty dict** when
            batching is off (the trace then contains no batching ops and
            shares the batching-free compile-cache entry).  When on:
            ``table`` (the padded speedup interpolation table, f64),
            ``bcap`` (scalar admissible-batch bound) and — only for the
            probed ``n_iter == 1`` peel — ``beff0`` (F, rows, T) f32,
            the host-computed iteration-1 batch occupancy the probe
            channel records.  The law itself is deposit-time scaling
            (see :mod:`repro.traffic.batching`): the decode-work and
            occupancy-count planes ride two extra chunk channels
            (``wdec``/``cntw``) through the same scatter, and the scan
            consumes ``work + work_dec * (1/s(B_eff) - 1)``.
        batch_window: Static — occupancy window in bins (0 when batching
            is off; >= 1 when on).
        probes: Static — ``None`` (the probe-free kernel, byte-identical
            to the pre-observability trace) or the resolved
            ``(capacity, stride)`` pair of a
            :class:`~repro.obs.probes.ProbeConfig`.  When set, the
            backlog/admission scans ring-write per-bin fleet state into
            ``pbuf`` via ``dynamic_update_slice`` (each fixed-point
            iteration rewrites the same slots, so the final iteration
            wins) and the output dict gains ``probes`` (the written
            buffers) plus ``probe_gw_wait``/``probe_ex_wait``
            (F, P, M, L) — the final per-token per-layer queue waits the
            flight recorder splices into the Eq. 43 breakdown.

    Returns:
        Dict of outputs with a leading F axis: ``ttft``/``e2e``
        (F, P, R), ``tok_total`` (F, P, M), ``tok_over`` (F, P, M) bool,
        ``shed``/``retries`` (F, P, R), ``work_sum`` (F, rows), iff
        ``want_wait`` — ``wait`` (T, F, rows) float32 — and iff
        ``probes`` the probe outputs described above.
    """
    q = consts
    first_tok, tok_req = q["first_tok"], q["tok_req"]
    F = ttft_target.shape[0]
    R = first_tok.shape[0]
    # Consts arrive plan-leading (shared across the sweep) on the
    # standard path and F-leading (per-sweep-entry gathers, the fused
    # control plane's schedule-row evaluation) on the joint-controller
    # path; ``lead`` gives the closures a broadcastable (F, P, ...) view
    # either way, and the plan-leading branch traces exactly the
    # pre-control-plane computation.
    fb = q["eff_layer"].ndim == 4
    if fb:
        _, P, M, L = q["eff_layer"].shape
    else:
        P, M, L = q["eff_layer"].shape

    def lead(x):
        return x if fb else x[None]

    T, SR = n_bins, n_rows
    dt = q["dt"]
    cap32, dt32 = q["cap32"], q["dt32"]
    f32, f64 = jnp.float32, jnp.float64

    def to_bins(times):
        finite = jnp.isfinite(times)
        b = jnp.clip((jnp.where(finite, times, 0.0) / dt)
                     .astype(jnp.int64), 0, T - 1)
        return jnp.where(finite, b, 0), finite

    if probes is not None:
        p_cap, p_stride = probes

    def probe_write(bufs, t, wait, w_t, drop, qhat=None, admit=None,
                    win=None, beff=None):
        # Ring write via dynamic_update_slice: bin t lands in slot
        # (t // stride) % capacity; bins the stride skips write the
        # sentinel scratch slot (index capacity), so the scan step is
        # branch-free and XLA keeps the buffers aliased in the carry.
        # Under batching a fourth row channel records the per-bin batch
        # occupancy B_eff.
        rec = (t % p_stride) == 0
        slot = jnp.where(rec, (t // p_stride) % p_cap, p_cap)
        chans = [wait, w_t, drop] + ([] if beff is None else [beff])
        out = dict(bufs)
        out["rows"] = jax.lax.dynamic_update_slice(
            bufs["rows"], jnp.stack(chans)[None],
            (slot, 0, 0, 0))
        if qhat is not None:
            out["aimd"] = jax.lax.dynamic_update_slice(
                bufs["aimd"], jnp.stack([qhat, win])[None],
                (slot, 0, 0, 0))
            out["admit"] = jax.lax.dynamic_update_slice(
                bufs["admit"], admit[None], (slot, 0, 0, 0))
        return out

    def schedule(gw_wait, ex_max, start_pref):
        # jnp port of FleetSim._schedule + ._chain (identical math),
        # batched over the leading F axis.
        lay_cost = lead(q["eff_layer"]) + gw_wait + ex_max
        tok_total = lead(q["tok_base"]) + gw_wait.sum(3) + ex_max.sum(3)
        dec = tok_total[:, :, R:]
        cs = jnp.cumsum(dec, axis=2)
        excl = cs - dec
        base = excl[:, :, first_tok][:, :, tok_req]
        c0 = start_pref + tok_total[:, :, :R]
        start_dec = c0[:, :, tok_req] + (excl - base)
        start_all = jnp.concatenate([start_pref, start_dec], axis=2)
        layer_arr = start_all[..., None] \
            + (jnp.cumsum(lay_cost, axis=3) - lay_cost)
        exp_arr = layer_arr + gw_wait + q["gw_service"][None, None, :, None]
        return layer_arr, exp_arr, tok_total, cs - base

    def bin_work(layer_arr, exp_arr, shed):
        # jnp port of FleetSim._bin_work: every active chunk reads its
        # event's arrival time straight from the F-flattened
        # [layer_arr | exp_arr] pair via the precomputed gather index,
        # then scatter-adds the row-major (F * rows, T) plane in f64
        # (chunks are statically row-grouped, so consecutive updates
        # stay within one row's cache-resident T-span).
        flat_t = jnp.concatenate([layer_arr.reshape(F, -1),
                                  exp_arr.reshape(F, -1)],
                                 axis=1).reshape(-1)
        b_ch, fin = to_bins(flat_t[chunks["src"]])
        bins = jnp.minimum(b_ch + chunks["offs"], T - 1)

        def scat(vals):
            if deposit_mode == "pallas":
                # TPU: one-hot-matmul deposit kernel (f32 accumulation —
                # TPUs have no f64; CPU CI parity runs the f64 paths).
                return _kernel_ops.deposit(
                    chunks["fprow"], bins.astype(jnp.int32),
                    vals.astype(f32), F * SR, T).astype(f64)
            if deposit_mode == "segments":
                # Non-TPU scatter relief: the chunk table is statically
                # row-grouped, so the flat ids are row-bucketed and one
                # stable sort feeds the sorted segment reduction —
                # bitwise identical to the reference scatter.
                return _kernel_ops.deposit_segments(
                    chunks["fprow"], bins, vals, F * SR, T)
            # int64 flat index: F * rows * T can exceed 2^31 on large
            # worlds/sweeps (x64 is enabled for every fused launch).
            flat = chunks["fprow"].astype(jnp.int64) * T + bins
            return jnp.zeros(F * SR * T).at[flat].add(
                vals, mode="promise_in_bounds")

        vals = chunks["work"] * fin
        if adm_on:
            # Shed requests stop depositing (the activity compaction
            # already removed thinned-out requests).
            keep = ~shed.reshape(-1)[chunks["fpr"]]
            vals = vals * keep
        work = scat(vals).reshape(F, SR, T)
        if "mig_dense" in q:
            work = work + q["mig_dense"][None]
        elif "mig_dense_f" in q:
            # Joint-controller evaluation: the migration background load
            # depends on the device-decided schedule, so it arrives as a
            # traced (F, rows, T) plane instead of a shared const.
            work = work + q["mig_dense_f"]
        if not batch:
            return work, work, None
        # Continuous batching (deposit-time scaling): the decode-work
        # and occupancy-count channels ride the same scatter, and the
        # scan consumes work + work_dec * (1/s(B_eff) - 1).  The
        # migration background plane stays outside work_dec — it is not
        # batchable decode work.
        vdec, vcnt = chunks["wdec"] * fin, chunks["cntw"] * fin
        if adm_on:
            vdec, vcnt = vdec * keep, vcnt * keep
        work_dec = scat(vdec).reshape(F, SR, T)
        cnt = scat(vcnt).reshape(F, SR, T)
        work_eff, beff = batched_effective_work(
            work, work_dec, windowed_counts_jnp(cnt, batch_window),
            batch["table"], batch["bcap"])
        return work_eff, work, beff

    def fleet_scan(work32, bufs=None, beff_t=None):
        # The _fleet_queue_scan backlog recursion, time-major and
        # wait-only (f32, exactly the legacy downcast).  With ring
        # buffers passed (the probed final iteration only), the scan
        # carry additionally threads them and every stride-th bin
        # records (backlog, offered work, dropped) — the bufs-free
        # branch below is byte-identical to the legacy scan.  With
        # ``beff_t`` (probed batching runs) the ring gains the
        # batch-occupancy channel.
        if bufs is None:
            def step(b, w_t):
                wait = b
                b = jnp.maximum(jnp.minimum(b + w_t, cap32) - dt32, 0.0)
                return b, wait
            _, wait = jax.lax.scan(step, jnp.zeros((F, SR), f32), work32)
            return wait                                   # (T, F, SR)

        def step(carry, xs):
            b, pb = carry
            if beff_t is None:
                (w_t, t), be = xs, None
            else:
                w_t, t, be = xs
            wait = b
            offered = b + w_t
            drop = jnp.maximum(offered - cap32, 0.0)
            pb = probe_write(pb, t, wait, w_t, drop, beff=be)
            b = jnp.maximum(jnp.minimum(offered, cap32) - dt32, 0.0)
            return (b, pb), wait
        xs = (work32, jnp.arange(T))
        if beff_t is not None:
            xs = xs + (beff_t,)
        (_, bufs), wait = jax.lax.scan(
            step, (jnp.zeros((F, SR), f32), bufs), xs)
        return wait, bufs

    def adm_scan(work32, bufs=None, beff_t=None):
        # The admission_queue_scan recursion (bit-identical backlog and
        # AIMD cell), time-major over compacted rows, emitting wait +
        # the admit trace.  With ring buffers passed (the probed final
        # iteration only), the carry also threads them, recording the
        # fleet channels plus the AIMD cell state (backlog estimate
        # qhat, per-gateway admit, window peak); the bufs-free branch
        # is byte-identical to the legacy scan.
        tt32 = ttft_target.astype(f32)[:, None, None]     # (F, 1, 1)
        tp32 = tpot_target.astype(f32)[:, None]           # (F, 1)
        n_layers = q["gw_rows_bin"].shape[-1]
        pid_on = "pid_kp" in q        # static: AIMD trace byte-identical

        def cell(state, w_t, is_ctrl, gw_t, exp_t):
            if pid_on:
                backlog, admit, win, integ, prev = state
            else:
                backlog, admit, win = state
            wait = backlog
            offered = backlog + w_t
            backlog = jnp.maximum(jnp.minimum(offered, cap32) - dt32, 0.0)
            if fb:
                # F-leading station maps: gw_t (F, P, L), exp_t (F, P, LI).
                fi = jnp.arange(F)[:, None, None]
                gw = backlog[fi, gw_t].sum(axis=2)               # (F, P)
                exp = backlog[fi, exp_t] \
                    .reshape(F, P, n_layers, -1).max(axis=3).sum(axis=2)
            else:
                gw = backlog[:, gw_t].sum(axis=2)                # (F, P)
                exp = backlog[:, exp_t] \
                    .reshape(F, P, n_layers, -1).max(axis=3).sum(axis=2)
            win = jnp.maximum(win, gw + exp)
            if pid_on:
                # PID cell (admission module docstring): same formula
                # order as the host scan so the laws agree bitwise.
                h_t = jnp.where(
                    jnp.isfinite(tt32),
                    (tt32 - (lead(q["ttft0"]) + win[..., None])) / tt32,
                    jnp.inf)                                     # (F,P,G)
                h_p = jnp.where(
                    jnp.isfinite(tp32),
                    (tp32 - (lead(q["tpot0"]) + win)) / tp32,
                    jnp.inf)[..., None]                          # (F,P,1)
                err = jnp.minimum(h_t, h_p)
                integ2 = jnp.minimum(
                    jnp.maximum(integ + err, -f32(_PID_WINDUP)),
                    f32(_PID_WINDUP))
                delta = (q["pid_kp"] * err + q["pid_ki"] * integ2
                         + q["pid_kd"] * (err - prev))
                stepped = jnp.minimum(
                    jnp.maximum(admit + q["pid_gain"][None, :, None]
                                * delta, q["admit_min"]), 1.0)
                admit_next = jnp.where(is_ctrl, stepped, admit)
                win_next = jnp.where(is_ctrl, 0.0, win)
                nstate = (backlog, admit_next, win_next,
                          jnp.where(is_ctrl, integ2, integ),
                          jnp.where(is_ctrl, err, prev))
            else:
                over = ((lead(q["ttft0"]) + win[..., None]) > tt32) \
                    | ((lead(q["tpot0"]) + win) > tp32)[..., None]
                stepped = jnp.where(
                    over,
                    jnp.maximum(admit * q["decrease"], q["admit_min"]),
                    jnp.minimum(admit + q["increase"], 1.0))
                admit_next = jnp.where(is_ctrl, stepped, admit)
                win_next = jnp.where(is_ctrl, 0.0, win)
                nstate = (backlog, admit_next, win_next)
            return nstate, wait, offered, gw + exp

        n_gw = q["ttft0"].shape[-1]
        carry0 = (jnp.zeros((F, SR), f32), jnp.ones((F, P, n_gw), f32),
                  jnp.zeros((F, P), f32))
        if pid_on:
            carry0 = carry0 + (jnp.zeros((F, P, n_gw), f32),
                               jnp.zeros((F, P, n_gw), f32))
        if bufs is None:
            def step(state, xs):
                w_t, is_ctrl, gw_t, exp_t = xs
                admit = state[1]
                state, wait, _, _ = cell(state, w_t, is_ctrl, gw_t, exp_t)
                return state, (wait, admit)
            _, (wait, admit) = jax.lax.scan(
                step, carry0,
                (work32, q["ctrl"], q["gw_rows_bin"], q["exp_rows_bin"]))
            return wait, admit             # (T, F, SR), (T, F, P, G)

        def step(carry, xs):
            state, pb = carry[:-1], carry[-1]
            if beff_t is None:
                (w_t, is_ctrl, gw_t, exp_t, t), be = xs, None
            else:
                w_t, is_ctrl, gw_t, exp_t, t, be = xs
            admit = state[1]
            state, wait, offered, qhat = cell(
                state, w_t, is_ctrl, gw_t, exp_t)
            drop = jnp.maximum(offered - cap32, 0.0)
            pb = probe_write(pb, t, wait, w_t, drop, qhat=qhat,
                             admit=state[1], win=state[2], beff=be)
            return state + (pb,), (wait, admit)
        xs = (work32, q["ctrl"], q["gw_rows_bin"], q["exp_rows_bin"],
              jnp.arange(T))
        if beff_t is not None:
            xs = xs + (beff_t,)
        out_carry, (wait, admit) = jax.lax.scan(
            step, carry0 + (bufs,), xs)
        return wait, admit, out_carry[-1]

    def gather(wait_t, work32, gw_b, gw_fin, ex_b, ex_fin):
        # jnp port of FleetSim._gather: wait read from the time-major
        # trace, work from the row-major plane; overload =
        # wait + work > cap is the legacy dropped > 0 flag.
        f_idx = jnp.arange(F)[:, None, None, None]
        gw_rows = lead(q["gw_rows"])                  # (1|F, P, M, L)
        ex_rows = lead(q["ex_rows"])                  # (1|F, P, M, L, K)
        w_g = wait_t[gw_b, f_idx, gw_rows]
        gw_wait = jnp.where(gw_fin, w_g, 0.0).astype(f64)
        gw_over = gw_fin & ((w_g + work32[f_idx, gw_rows, gw_b]) > cap32)
        ex_b5, ex_f5 = ex_b[..., None], ex_fin[..., None]
        f_idx5 = f_idx[..., None]
        w_e = wait_t[ex_b5, f_idx5, ex_rows]
        ex_wait = jnp.where(ex_f5, w_e, 0.0).astype(f64)
        ex_over = ex_f5 & ((w_e + work32[f_idx5, ex_rows, ex_b5]) > cap32)
        return gw_wait, ex_wait.max(axis=4), gw_over, ex_over.any(axis=4)

    def finish_iter(work32, work_sum, gw_b, gw_fin, ex_b, ex_fin, c,
                    record=False, beff=None):
        # Scan + admission resolve + gather for one iteration whose
        # offered work (f32, row-major (F, SR, T)) is already binned;
        # only the scan input is transposed to time-major.  ``record``
        # (static) threads the probe rings through this iteration's
        # scan — set on the peeled *final* iteration only, so the probe
        # cost is paid once per launch, not once per iteration.  Under
        # batching ``work32`` is the *effective* (speedup-scaled) work —
        # gather overload stays consistent with the scan — while
        # ``work_sum`` stays the raw offered sum; ``beff`` feeds the
        # recorded batch-occupancy probe channel.
        work32_t = jnp.moveaxis(work32, 2, 0)             # (T, F, SR)
        beff_t = None
        if record and beff is not None:
            beff_t = jnp.moveaxis(beff.astype(f32), 2, 0)
        pb = c.get("probes")
        if adm_on:
            if not record:
                wait_t, admit = adm_scan(work32_t)
            else:
                wait_t, admit, pb = adm_scan(work32_t, pb, beff_t)
            # Monotone outer iteration (see run_legacy): the admit trace
            # accumulates as a running minimum so the shed set only grows.
            admit_floor = jnp.minimum(c["admit_floor"], admit)
            if q["att_bin"].ndim == 3:
                # Federation lanes: the attempt tables ride a leading F
                # axis (each member constellation's retry gateways and
                # arrival bins follow its own ground visibility), so
                # the admit trace is read per (lane, attempt, request).
                fi = jnp.arange(F)[:, None, None]
                adm = jnp.moveaxis(
                    admit_floor[q["att_bin"], fi, :, q["att_station"]],
                    3, 1)                                 # (F, P, A, R)
            else:
                adm = jnp.transpose(
                    admit_floor[q["att_bin"], :, :, q["att_station"]],
                    (2, 3, 0, 1))                         # (F, P, A, R)
            u = (q["adm_u"][:, None] if q["adm_u"].ndim == 3
                 else q["adm_u"][None, None])
            ok = (u < adm) & lead(q["att_feasible"])
            shed = ~ok.any(axis=2)                        # (F, P, R)
            retries = jnp.where(shed, 0, jnp.argmax(ok, axis=2))
            att_x = q["att_extra"] if fb else jnp.broadcast_to(
                q["att_extra"][None], (F,) + q["att_extra"].shape)
            ingress_extra = jnp.take_along_axis(
                att_x, retries[:, :, None, :], axis=2)[:, :, 0, :]
        else:
            if not record:
                wait_t = fleet_scan(work32_t)
            else:
                wait_t, pb = fleet_scan(work32_t, pb, beff_t)
            shed, retries = c["shed"], c["retries"]
            admit_floor = c["admit_floor"]
            ingress_extra = c["ingress_extra"]
        gw_wait, ex_max, gw_over, ex_over = gather(
            wait_t, work32, gw_b, gw_fin, ex_b, ex_fin)
        nxt = dict(gw_wait=gw_wait, ex_max=ex_max, gw_over=gw_over,
                   ex_over=ex_over, shed=shed, retries=retries,
                   admit_floor=admit_floor, ingress_extra=ingress_extra,
                   work_sum=work_sum)
        if want_wait:
            nxt["wait"] = wait_t
        if record:
            nxt["probes"] = pb
        return nxt

    def body(_, c, record=False):
        start_pref = q["arrival_s"][None, None, :] + c["ingress_extra"]
        layer_arr, exp_arr, _, _ = schedule(c["gw_wait"], c["ex_max"],
                                            start_pref)
        work, work_raw, beff = bin_work(layer_arr, exp_arr,
                                        c["shed"])       # (F, SR, T)
        gw_b, gw_fin = to_bins(layer_arr)
        ex_b, ex_fin = to_bins(exp_arr)
        return finish_iter(work.astype(f32), work_raw.sum(axis=2),
                           gw_b, gw_fin, ex_b, ex_fin, c, record=record,
                           beff=beff)

    n_gw = q["ttft0"].shape[-1] if adm_on else 1
    carry = dict(
        gw_wait=jnp.zeros((F, P, M, L)), ex_max=jnp.zeros((F, P, M, L)),
        gw_over=jnp.zeros((F, P, M, L), bool),
        ex_over=jnp.zeros((F, P, M, L), bool),
        shed=jnp.zeros((F, P, R), bool),
        retries=jnp.zeros((F, P, R), jnp.int64),
        admit_floor=jnp.ones((T, F, P, n_gw), jnp.float32),
        ingress_extra=(q["ingress_extra0"] + 0.0) if fb
        else jnp.broadcast_to(q["ingress_extra0"][None], (F, P, R)) + 0.0,
        work_sum=jnp.zeros((F, SR)),
    )
    if want_wait:
        carry["wait"] = jnp.zeros((T, F, SR), f32)
    # Peeled iteration 1: the zero-wait schedule is static, so its
    # offered work arrives pre-binned (host np.bincount) and its gather
    # bins are construction-time constants.  With probes on, the *last*
    # iteration is peeled too (its probe-recording scan is traced
    # separately), so ring writes happen exactly once per launch.
    if probes is None:
        carry = finish_iter(work0, work0_sum,
                            lead(q["gw_b0"]), lead(q["gw_fin0"]),
                            lead(q["ex_b0"]), lead(q["ex_fin0"]), carry)
        c = jax.lax.fori_loop(0, n_iter - 1, body, carry)
    elif n_iter == 1:
        carry["probes"] = pbuf
        # Peeled-final batching runs ship the host-computed iteration-1
        # occupancy (batch["beff0"]) for the probe channel; work0 itself
        # is already the host-computed effective plane.
        c = finish_iter(work0, work0_sum,
                        lead(q["gw_b0"]), lead(q["gw_fin0"]),
                        lead(q["ex_b0"]), lead(q["ex_fin0"]), carry,
                        record=True, beff=batch.get("beff0"))
    else:
        carry = finish_iter(work0, work0_sum,
                            lead(q["gw_b0"]), lead(q["gw_fin0"]),
                            lead(q["ex_b0"]), lead(q["ex_fin0"]), carry)
        c = jax.lax.fori_loop(0, n_iter - 2, body, carry)
        c["probes"] = pbuf
        c = body(0, c, record=True)
    # Fold the final gather into the schedule once more (see run_legacy).
    start_pref = q["arrival_s"][None, None, :] + c["ingress_extra"]
    _, _, tok_total, seg_incl = schedule(c["gw_wait"], c["ex_max"],
                                         start_pref)
    ttft = c["ingress_extra"] + tok_total[:, :, :R]
    out = dict(ttft=ttft, e2e=ttft + seg_incl[:, :, q["last_tok"]],
               tok_total=tok_total,
               tok_over=c["gw_over"].any(axis=3) | c["ex_over"].any(axis=3),
               shed=c["shed"], retries=c["retries"],
               work_sum=c["work_sum"])
    if want_wait:
        out["wait"] = c["wait"]
    if probes is not None:
        out["probes"] = c["probes"]
        out["probe_gw_wait"] = c["gw_wait"]
        out["probe_ex_wait"] = c["ex_max"]
    return out


def _fused_core(consts, chunks, work0, work0_sum, ttft_target, tpot_target,
                pbuf, batch, n_iter, n_bins, n_rows, adm_on, deposit_mode,
                want_wait, probes, batch_window):
    """Counting wrapper around :func:`_fleet_fixed_point` — the body the
    standalone jits below trace.  The trace counter lives here (not in
    the fixed point itself) so the joint-controller kernel, which embeds
    several fixed points in one program, still counts one trace per
    launch shape."""
    global FUSED_TRACE_COUNT
    FUSED_TRACE_COUNT += 1
    return _fleet_fixed_point(
        consts, chunks, work0, work0_sum, ttft_target, tpot_target, pbuf,
        batch, n_iter, n_bins, n_rows, adm_on, deposit_mode, want_wait,
        probes, batch_window)


#: The jitted fused fixed point.  Statics: (n_iter, n_bins, n_rows,
#: adm_on, deposit_mode, want_wait, probes, batch_window); everything else
#: rides the pytrees, so any fleet run with equal shapes — every rate of
#: a sweep, every re-placement decide/evaluate round — hits one compile
#: cache entry.  Probe-free launches pass ``probes=None`` and an empty
#: pbuf pytree, and batching-free launches an empty ``batch`` pytree
#: with ``batch_window=0``, so their traced computation is byte-identical
#: to the legacy kernel.
_fused_exec = jax.jit(_fused_core,
                      static_argnums=(8, 9, 10, 11, 12, 13, 14, 15))

#: Probed variant: identical statics, but the probe ring buffers
#: (positional arg 6) are donated so XLA updates them in place instead
#: of copying the rings once per scan step.
_fused_exec_probed = jax.jit(_fused_core,
                             static_argnums=(8, 9, 10, 11, 12, 13, 14, 15),
                             donate_argnums=(6,))


class _CtrlMeta(NamedTuple):
    """Static (hashable) configuration of the joint-controller kernel.

    One value per compile-relevant scalar of :func:`_ctrl_core`; grids
    that share a meta share one trace, which is what the
    ``FUSED_TRACE_COUNT`` acceptance pin counts.
    """

    n_iter: int          #: schedule<->queue fixed-point iterations
    n_bins: int          #: T, time bins
    n_rows: int          #: compact (plan, satellite) rows of the probe
    n_rows_sched: int    #: compact satellite rows of the schedule row
    n_cand: int          #: C, candidate-pool size
    n_slots: int         #: N_T, topology slots
    n_bounds: int        #: last decision boundary index (see replan.py)
    n_rounds: int        #: controller decide+evaluate rounds
    adm_on: bool         #: admission regime active
    deposit_mode: str    #: "pallas" | "segments" | "ref" (see _launch)
    mode_backlog: bool   #: backlog-inflated scoring (vs base-only)
    hysteresis: float    #: relative switching threshold
    ref_q: float         #: admission reference quantile (0 if adm off)
    decide_bins: tuple   #: per-boundary backlog observation bin
    n_mig_chunks: int    #: dt-chunks one migration transfer spans
    mig_bounds: tuple    #: (prev_slot, cur_slot, first_bin) per boundary


def _ctrl_core(consts, chunks, work0, work0_sum, ttft_target, tpot_target,
               cc, meta):
    """The joint control plane: probe -> decide -> evaluate in ONE launch.

    Embeds several :func:`_fleet_fixed_point` fixed points in a single
    device program, batched over a leading controller-grid axis F
    (cadence x migration-budget x admission-target cells):

    1. **probe** — the candidate pool's fleet fixed point (exactly the
       ``_fused_core`` computation ``FleetSim.run`` launches), whose
       backlog trace is the controller's observation *and* the shared
       qhat signal the admission scan reads;
    2. **decide** — the pinned re-placement law of
       ``repro.traffic.replan`` (backlog-inflated scores, hysteresis
       gate, migration-cost gate) as array ops over that trace, walking
       the slot boundaries with a per-cell cadence mask;
    3. **evaluate** — a second fixed point over the decided
       schedule row, whose consts are *gathers* of the candidate
       tables by the decided plan-per-slot (tokens of slot n traverse
       plan ``slot_plan[n]``), with the migration background load
       deposited from the decided switch pairs in the same pass.

    Backlog mode refines: rounds 2..n_rounds re-decide against the
    evaluation's own backlog and re-evaluate — the device always runs
    the full ``controller_iterations`` rounds where the host loop may
    break early on a fixed point, which is equivalent because the
    evaluation is a deterministic function of the slot plan.

    Every arithmetic step replicates the host controller bit-for-bit on
    CPU: the score penalty reproduces numpy's pairwise summation, the
    admission anchors reproduce ``np.quantile``'s interpolation, and the
    schedule row's chunk table is ordered event-major so each
    (row, bin) accumulates its float64 deposits in the exact order of a
    host-built evaluation simulator.

    Args:
        consts: The probe's device tables (plan-leading).
        chunks: The probe's all-active compacted chunk table, built at
            the deduplicated admission-cell width F_u (see the probe
            dedup note in the body).
        work0/work0_sum: Probe peeled-iteration planes (F_u-wide).
        ttft_target/tpot_target: (F,) margin-scaled admission targets
            (the evaluation fixed points still need per-cell targets).
        cc: Controller tables pytree (:meth:`FleetSim._ctrl_tables`
            plus per-grid arrays: base scores, decide mask, migration
            weights and priced byte matrix).
        meta: Static :class:`_CtrlMeta`.

    Returns:
        ``slot_plan`` (F, N_T), the decision ``telem`` pytree
        (scores/chosen/switched/mig_bytes over boundaries), and the
        kept outputs of the probe and schedule-row fixed points.
    """
    global FUSED_TRACE_COUNT
    FUSED_TRACE_COUNT += 1
    q = consts
    F = ttft_target.shape[0]
    C, T, SRs = meta.n_cand, meta.n_bins, meta.n_rows_sched
    P, M, L = q["eff_layer"].shape
    R = q["first_tok"].shape[0]
    f32, f64 = jnp.float32, jnp.float64
    f_i = jnp.arange(F)

    # The probe depends on the admission-target axis alone — cells that
    # share a (TTFT, TPOT) target share a probe fixed point.  The host
    # side deduplicated the targets (``probe_ttft``/``probe_tpot``,
    # width F_u <= F) and supplies the inverse map ``probe_gather``:
    # the probe runs F_u-wide and its outputs are gathered back to F,
    # bitwise identical to computing every duplicate (each cell's row
    # is an independent, deterministic batch lane).  A cadence x
    # migration-budget grid with one admission target probes ONCE.
    probe = _fleet_fixed_point(
        q, chunks, work0, work0_sum, cc["probe_ttft"], cc["probe_tpot"],
        {}, {}, meta.n_iter, T, meta.n_rows, meta.adm_on,
        meta.deposit_mode, True, None, 0)
    pg = cc["probe_gather"]
    probe = {k: (v[:, pg] if k == "wait" else v[pg])
             for k, v in probe.items()}

    def np_sum(x):
        # numpy pairwise-summation replica over the last axis (the host
        # score penalty sums float32 backlog slices with np.sum; the
        # parity pin needs the identical partial-sum tree).
        def pair(y, n):
            if n < 8:
                res = jnp.zeros(y.shape[:-1], y.dtype)
                for i in range(n):
                    res = res + y[..., i]
                return res
            if n <= 128:
                r = [y[..., j] for j in range(8)]
                i = 8
                while i + 8 <= n:
                    for j in range(8):
                        r[j] = r[j] + y[..., i + j]
                    i += 8
                res = ((r[0] + r[1]) + (r[2] + r[3])) \
                    + ((r[4] + r[5]) + (r[6] + r[7]))
                while i < n:
                    res = res + y[..., i]
                    i += 1
                return res
            n2 = (n // 2) - ((n // 2) % 8)
            return pair(y[..., :n2], n2) + pair(y[..., n2:], n - n2)
        return pair(x, x.shape[-1])

    zero_col = jnp.zeros((F, 1), f32)

    def penalty(wait_b, rows_gw, rows_ex):
        # replan.backlog_penalty_s: gateway backlog sum + per-layer max
        # expert backlog sum, read off one backlog snapshot.  A sentinel
        # row (== n_rows) indexes the appended zero column — the host's
        # expansion to all satellites reads 0.0 at compacted-out rows.
        w = jnp.concatenate([wait_b, zero_col], axis=1)
        g = w[f_i[:, None, None], rows_gw]                  # (F, C, L)
        e = w[f_i[:, None, None, None], rows_ex]            # (F, C, L, I)
        return (np_sum(g) + np_sum(e.max(axis=3))).astype(f64)

    def decide(wait, rows_gw_of, rows_ex_of):
        # The verbatim decide law of replan.build_replan_schedule,
        # vectorized over grid cells: per boundary k the cell's cadence
        # mask arbitrates whether the (hysteresis + migration-cost)
        # gated argmin replaces the incumbent.
        cur = jnp.zeros(F, dtype=jnp.int64)
        plan_cols, t_sc, t_cur, t_sw, t_mb = [], [], [], [], []
        for k in range(meta.n_bounds + 1):
            scores = jnp.broadcast_to(cc["base_scores"][k][None], (F, C))
            if meta.mode_backlog and k > 0:
                scores = scores + penalty(wait[meta.decide_bins[k]],
                                          rows_gw_of(cur), rows_ex_of(cur))
            best = jnp.argmin(scores, axis=1)
            if k == 0:
                nxt, switched, mb = best, jnp.zeros(F, bool), jnp.zeros(F)
            else:
                sc_cur = scores[f_i, cur]
                gain = sc_cur - scores[f_i, best]
                moved = cc["bytes_mat"][cur, best]
                gate = meta.hysteresis * sc_cur + moved * cc["mig_w"] / 1e6
                switched = (best != cur) & (gain > gate)
                nxt = jnp.where(switched, best, cur)
                mb = jnp.where(switched, moved, 0.0)
            dk = cc["decide_mask"][:, k]
            cur = jnp.where(dk, nxt, cur)
            plan_cols.append(cur)
            t_sc.append(scores)
            t_cur.append(cur)
            t_sw.append(switched & dk)
            t_mb.append(jnp.where(dk, mb, 0.0))
        cols = plan_cols + [cur] * (meta.n_slots - (meta.n_bounds + 1))
        telem = dict(scores=jnp.stack(t_sc, axis=1),
                     chosen=jnp.stack(t_cur, axis=1),
                     switched=jnp.stack(t_sw, axis=1),
                     mig_bytes=jnp.stack(t_mb, axis=1))
        return jnp.stack(cols, axis=1), telem

    def masked_quantile(vals, mask):
        # np.quantile (linear interpolation) over a masked last axis —
        # including numpy's _lerp asymmetry around t = 0.5, which the
        # bitwise admission-anchor parity needs.
        n = vals.shape[-1]
        s = jnp.sort(jnp.where(mask, vals, jnp.inf), axis=-1)
        nv = mask.sum(axis=-1)
        vi = meta.ref_q * (nv - 1).astype(f64)
        lo = jnp.clip(jnp.floor(vi), 0.0, None)
        t = vi - lo
        lo_i = lo.astype(jnp.int64)
        hi_i = jnp.minimum(lo_i + 1, jnp.maximum(nv - 1, 0))
        a = jnp.take_along_axis(s, jnp.clip(lo_i, 0, n - 1)[..., None],
                                axis=-1)[..., 0]
        b = jnp.take_along_axis(s, jnp.clip(hi_i, 0, n - 1)[..., None],
                                axis=-1)[..., 0]
        d = b - a
        out = jnp.where(t >= 0.5, b - d * (1.0 - t), a + d * t)
        return jnp.where(nv > 0, out, 0.0)

    mi = jnp.arange(M)[None]
    ri = jnp.arange(R)[None]

    def eval_consts(sp):
        # Schedule-row device tables: per-token / per-request gathers of
        # the candidate tables by the decided plan of the token's slot
        # (P axis = 1, F-leading — the fixed point's ``fb`` branch).
        pt = sp[:, cc["slot_tok"]]                          # (F, M)
        pr = pt[:, :R]
        eq = dict(dt=q["dt"], cap32=q["cap32"], dt32=q["dt32"],
                  gw_service=q["gw_service"], arrival_s=q["arrival_s"],
                  first_tok=q["first_tok"], tok_req=q["tok_req"],
                  last_tok=q["last_tok"],
                  eff_layer=q["eff_layer"][pt, mi][:, None],
                  tok_base=q["tok_base"][pt, mi][:, None],
                  ingress_extra0=q["ingress_extra0"][pr, ri][:, None],
                  gw_rows=cc["gw_srow"][pt, mi][:, None],
                  ex_rows=cc["ex_srow"][pt, mi][:, None],
                  gw_b0=q["gw_b0"][pt, mi][:, None],
                  gw_fin0=q["gw_fin0"][pt, mi][:, None],
                  ex_b0=q["ex_b0"][pt, mi][:, None],
                  ex_fin0=q["ex_fin0"][pt, mi][:, None])
        if meta.n_mig_chunks and meta.mig_bounds:
            # Migration background load of the decided switches: exact
            # sequential-sum tables per (incumbent, successor) pair,
            # deposited at each boundary's bins.
            plane = jnp.zeros((F, SRs, T))
            for prev_s, cur_s, b0 in meta.mig_bounds:
                pv = cc["mig_plane"][:, sp[:, prev_s], sp[:, cur_s]]
                for j in range(meta.n_mig_chunks):
                    plane = plane.at[:, :, min(b0 + j, T - 1)].add(pv[j])
            eq["mig_dense_f"] = plane
        if meta.adm_on:
            # Re-derive the schedule row's admission anchors (the
            # reference-quantile zero-load latencies) from the decided
            # per-request plan — the joint-controller face of
            # _build_admission_tables.
            G = q["ttft0"].shape[-1]
            ok = cc["adm_ok0"][pr, ri]
            bt = cc["adm_base_ttft"][pr, ri]
            overall = masked_quantile(bt, ok)
            selg = ok[:, None, :] & (cc["adm_station"][None, None, :]
                                     == jnp.arange(G)[None, :, None])
            per_g = masked_quantile(
                jnp.broadcast_to(bt[:, None], (F, G, R)), selg)
            ttft0 = jnp.where(selg.any(axis=2), per_g, overall[:, None])
            ni = jnp.arange(M - R)[None]
            pd = pt[:, R:]
            tpot0 = masked_quantile(cc["adm_dec_vals"][pd, ni],
                                    cc["adm_dec_ok"][pd, ni])
            pb = sp[:, cc["slot_of_bin"]]                   # (F, T)
            ti = jnp.arange(T)[:, None]
            eq.update(
                ttft0=ttft0[:, None].astype(f32),
                tpot0=tpot0[:, None].astype(f32),
                ctrl=q["ctrl"], increase=q["increase"],
                decrease=q["decrease"], admit_min=q["admit_min"],
                att_bin=q["att_bin"], att_station=q["att_station"],
                adm_u=q["adm_u"],
                gw_rows_bin=cc["gw_srow_bin"][ti, pb.T][:, :, None],
                exp_rows_bin=cc["exp_srow_bin"][ti, pb.T][:, :, None],
                att_feasible=jnp.transpose(
                    cc["att_feas_c"][pr, :, ri], (0, 2, 1))[:, None],
                att_extra=jnp.transpose(
                    cc["att_extra_c"][pr, :, ri], (0, 2, 1))[:, None])
            if "pid_kp" in q:
                # Per-plan gains are gated off by run_replan_grid, so
                # the schedule row runs at unit gain like every plan.
                eq.update(pid_kp=q["pid_kp"], pid_ki=q["pid_ki"],
                          pid_kd=q["pid_kd"],
                          pid_gain=jnp.ones((1,), jnp.float32))
        return eq

    n_gate = cc["ch_work"].shape[0]

    def eval_launch(sp):
        # The schedule row's fixed point: the probe's event-major chunk
        # table rides along gated per chunk by "is this chunk's plan the
        # decided plan of its request's slot" — multiplying by the 0/1
        # gate keeps deposits exact (interleaved zero adds are f64
        # no-ops), so the (row, bin) accumulation order matches a
        # host-built evaluation simulator bit for bit.
        eq = eval_consts(sp)
        gate = (sp[:, cc["ch_slot"]] == cc["ch_plan"][None]).astype(f64)
        ech = dict(
            src=(f_i[:, None] * (2 * M * L)
                 + cc["ch_local"][None]).reshape(-1),
            offs=jnp.broadcast_to(cc["ch_offs"][None],
                                  (F, n_gate)).reshape(-1),
            work=(cc["ch_work"][None] * gate).reshape(-1),
            fprow=(f_i[:, None] * SRs
                   + cc["ch_srow"][None]).astype(jnp.int32).reshape(-1))
        if meta.adm_on:
            ech["fpr"] = (f_i[:, None] * R
                          + cc["ch_req"][None]).reshape(-1)
        v0 = ((cc["ch_work"] * cc["ch_fin0"])[None] * gate).reshape(-1)
        bins0 = jnp.broadcast_to(cc["ch_bins0"][None],
                                 (F, n_gate)).reshape(-1)
        if meta.deposit_mode == "pallas":
            plane0 = _kernel_ops.deposit(
                ech["fprow"], bins0.astype(jnp.int32), v0.astype(f32),
                F * SRs, T).astype(f64).reshape(F, SRs, T)
        elif meta.deposit_mode == "segments":
            plane0 = _kernel_ops.deposit_segments(
                ech["fprow"], bins0, v0, F * SRs, T).reshape(F, SRs, T)
        else:
            flat0 = ech["fprow"].astype(jnp.int64) * T + bins0
            plane0 = jnp.zeros(F * SRs * T).at[flat0].add(
                v0, mode="promise_in_bounds").reshape(F, SRs, T)
        if "mig_dense_f" in eq:
            plane0 = plane0 + eq["mig_dense_f"]
        return _fleet_fixed_point(
            eq, ech, plane0.astype(f32), plane0.sum(axis=2),
            ttft_target, tpot_target, {}, {}, meta.n_iter, T, SRs,
            meta.adm_on, meta.deposit_mode, True, None, 0)

    # Round 1 decides against the probe's backlog (per incumbent row);
    # backlog-mode refinement rounds re-decide against the decided
    # schedule's own backlog (incumbent-independent maps).
    sp, telem = decide(probe["wait"],
                       lambda cur: cc["pen1_gw"][cur],
                       lambda cur: cc["pen1_ex"][cur])
    ev = eval_launch(sp)
    for _ in range(meta.n_rounds - 1):
        sp, telem = decide(ev["wait"],
                           lambda cur: cc["pen2_gw"][None],
                           lambda cur: cc["pen2_ex"][None])
        ev = eval_launch(sp)
    keep = ("ttft", "e2e", "tok_total", "tok_over", "shed", "retries",
            "work_sum")
    return dict(slot_plan=sp, telem=telem,
                probe={k: probe[k] for k in keep},
                sched={k: ev[k] for k in keep})


#: The jitted joint-controller kernel.  Exactly one trace per
#: (_CtrlMeta, pytree shape) — a whole cadence x migration-budget x
#: admission-target grid batches the leading axis of one launch.
_ctrl_exec = jax.jit(_ctrl_core, static_argnums=(7,))


# --------------------------------------------------------------------- #
# The fleet simulator
# --------------------------------------------------------------------- #


class FleetSim:
    """Request-level serving simulator for a sweep of placement plans
    *or* time-indexed :class:`~repro.core.schedule.PlanSchedule` entries
    (plain plans are wrapped into constant schedules, which reproduce
    the PR-2 static behavior bit-for-bit).

    Queue stations are keyed by **satellite id** — one FIFO work queue
    per satellite of the constellation (S = V).  Colocated experts share
    their satellite's queue by construction (the queue-theoretic face of
    Eq. 43), and a schedule that switches plans at a topology-slot
    boundary points new deposits at the incoming plan's satellites while
    the outgoing plan's backlog drains where it sits — the mechanism
    that makes live re-placement pay.  The weight bytes a switch moves
    (:meth:`~repro.core.schedule.PlanSchedule.migration_edges`, the
    ``distributed.elastic`` accounting) occupy each moved expert's
    destination-satellite queue as background load.

    Construction does all the rate-independent precompute: one batched
    engine pass over R prefill macro-tokens + N decode tokens (shared
    slots/draws across plans — common random numbers), the zero-load
    per-layer costs, every queue event's (plan, station, request, work)
    and the chunk layout.  ``run`` then iterates the schedule/queue
    fixed point for any request-activity mask — the cheap inner call of
    a saturation sweep.

    When ``qcfg.admission`` enables the AIMD policy, construction also
    precomputes the gateway-retry attempt tables (per attempt: target
    gateway, terrestrial forward + backoff + uplink + ingress-offset
    latency, feasibility) and the controller's zero-load TTFT/TPOT
    references; ``run`` then resolves per-request admission between
    fixed-point iterations from the controller trace the fleet scan
    emits (see :mod:`repro.traffic.admission` for the law).
    """

    def __init__(
        self,
        plans: list,
        topo: TopologySample,
        activation: ActivationModel,
        workload: MoEWorkload,
        compute: ComputeConfig,
        requests: RequestBatch,
        rng: np.random.Generator,
        qcfg: QueueConfig = QueueConfig(),
        ground: GroundSegment | None = None,
        ctx_len: int = 1024,
        eta: float = 1.0,
        include_lm_head: bool = True,
        batch: ScheduleBatch | None = None,
        min_bins: int = 0,
        service_model=None,
        probes: ProbeConfig | None = None,
        batching: BatchingConfig | None = None,
    ):
        """Build the simulator and run every rate-independent precompute.

        Args:
            plans: Sweep entries (P of them): plain
                :class:`~repro.core.placement.PlacementPlan` /
                :class:`~repro.core.placement.MultiExpertPlan` (held for
                the whole horizon) and/or time-indexed
                :class:`~repro.core.schedule.PlanSchedule` rows, mixed
                freely.
            topo: Sampled time-varying topology the engine pass uses.
            activation: Conditional-Poisson expert-activation model.
            workload: Per-component FLOP model of the served MoE.
            compute: FLOPs -> seconds conversion for onboard compute.
            requests: The request trace (R requests, sorted arrivals).
            rng: Source of the engine's expert draws and the admission
                uniforms (consumed at construction; runs are replayable).
            qcfg: Queueing/admission parameters.
            ground: Optional ground segment; enables uplink + ingress
                accounting and (under AIMD admission) gateway retry.
            ctx_len: Attention context length for gateway service time.
            eta: Eq. 43 compute-sharing efficiency for multi-expert plans.
            include_lm_head: Account lm-head service on the last gateway.
            batch: Optional prebuilt :class:`~repro.core.ScheduleBatch`
                to reuse the deduped Dijkstra table across simulators.
            min_bins: Floor on the time-bin count T.  The re-placement
                loop pins consecutive decide/evaluate rounds to one T so
                every round's fleet run reuses the fused fixed point's
                compile cache (a longer natural horizon still wins).
            service_model: Eq. 43 service-time source — ``None`` /
                ``"analytic"`` keeps the FLOP-count constants
                (bit-identical to the pre-calibration simulator), a
                calibrated :class:`~repro.core.calibration.ServiceModel`
                activates kernel-calibrated per-expert / per-satellite
                service and batch-size-dependent decode gateway rates
                (weight reads amortized over the estimated in-flight
                decode batch, read off the decode-attention roofline).
            probes: Optional :class:`~repro.obs.probes.ProbeConfig`.
                When set, every launch writes on-device telemetry rings
                (per-bin backlog / offered work / drops per satellite,
                plus the AIMD cell state under admission) that land in
                :attr:`last_probes` as a
                :class:`~repro.obs.probes.ProbeRecord`.  ``None`` (the
                default) keeps the fused kernel's traced computation
                bit-identical to the probe-free simulator.
            batching: Optional
                :class:`~repro.traffic.batching.BatchingConfig`.  When
                set, per-(plan, satellite) decode queues drain in
                batches of up to ``b_max`` per time bin with service
                time ``B / decode_rate(B)`` and KV-slot occupancy
                bounding the admissible batch (deposit-time scaling —
                see :mod:`repro.traffic.batching`).  ``None`` (the
                default) keeps every execution path bit-identical to
                the FIFO simulator, and so does ``b_max=1``.
        """
        self.plans = list(plans)
        self.schedules = [as_schedule(p, topo.n_slots) for p in self.plans]
        self.requests = requests
        self.qcfg = qcfg
        self.activation = activation
        # Stashed for the joint control plane (``run(replan=...)`` /
        # :meth:`run_replan_grid`): the base-score sweep re-enters the
        # batched plan engine at decision time.
        self.topo = topo
        self.workload = workload
        self.compute = compute

        P = len(self.schedules)
        R = requests.n_requests
        if R == 0:
            raise ValueError("empty request trace")
        L = activation.n_layers
        n_exp = activation.n_experts
        K = activation.top_k
        N = requests.total_decode_tokens
        M = R + N
        self.n_plans, self.n_requests = P, R
        self.n_decode_tokens, self.n_tokens = N, M
        # One FIFO work queue per satellite of the constellation.
        self.n_layers, self.n_stations = L, topo.n_sats
        self.n_topo_slots = topo.n_slots

        tok_req = requests.request_of_token()                    # (N,)
        self.tok_req = tok_req

        # --- slots from wall-clock time (one slot per request: request
        # lifetimes are seconds, a topology slot is minutes) ---------------
        slot_r = slot_of_time(requests.arrival_s, qcfg.slot_period_s,
                              topo.n_slots)
        self.slots = np.concatenate([slot_r, slot_r[tok_req]])   # (M,)

        # --- ingress mapping ----------------------------------------------
        if batch is None:
            batch = ScheduleBatch.from_schedules(self.schedules, topo,
                                                 eta=eta)
        self.batch = batch
        if ground is not None:
            ing_sat, uplink = ground.for_requests(slot_r, requests.station)
            reachable = ing_sat >= 0
            ing_off = schedule_ingress_offsets(
                batch, slot_r, np.where(reachable, ing_sat, 0))
            ing_off = np.where(reachable[None, :], ing_off, np.inf)
        else:
            uplink = np.zeros(R)
            ing_off = np.zeros((P, R))
        self.fail_ingress = ~np.isfinite(ing_off)                 # (P, R)
        self.ingress_extra = uplink[None, :] + np.where(
            self.fail_ingress, 0.0, ing_off)                      # (P, R)

        # --- engine pass: base (zero-load) per-token latencies -------------
        svc = resolve_service_model(service_model, workload, compute)
        self.service_model = svc
        # Continuous-batching statics: the padded speedup table (read
        # off the service model's batch-size-dependent decode rates),
        # the KV-bounded batch cap, and the occupancy window in bins.
        self.batching = batching
        if batching is not None:
            self._batch_table = batching.resolve_table(svc, ctx_len)
            self._batch_cap = float(batching.b_cap)
            self._batch_window = batching.window_bins(qcfg.dt_s)
        else:
            self._batch_table = None
            self._batch_cap = 0.0
            self._batch_window = 0
        draws = np.stack([activation.sample(layer, rng, M)
                          for layer in range(L)])                 # (L, M, K)
        self.draws = draws
        self.engine_results = evaluate_schedules(
            self.schedules, topo, activation, workload, compute, rng,
            n_tokens=M, ctx_len=ctx_len, include_lm_head=include_lm_head,
            eta=eta, batch=batch, slots=self.slots, draws=draws,
            service_model=svc)
        token_lat = np.stack(
            [r.token_latency_s for r in self.engine_results])     # (P, M)
        layer_lat = np.stack(
            [r.layer_latency_s for r in self.engine_results])     # (P, M, L)

        # Undeliverable tokens (unreachable satellite in that slot) fail
        # the whole request; zero them so the segmented cumsums of the
        # *other* requests sharing the token axis stay finite.
        self.nan_tok = ~np.isfinite(token_lat)
        token_lat = np.where(self.nan_tok, 0.0, token_lat)
        layer_lat = np.where(np.isfinite(layer_lat), layer_lat, 0.0)

        t_gateway = svc.gateway_s(ctx_len)
        t_expert = svc.expert_scalar
        t_head = svc.head_s if include_lm_head else 0.0
        self.t_gateway, self.t_expert = t_gateway, t_expert

        # --- zero-load per-layer costs -------------------------------------
        # Prefill macro-token: the engine token plus, per layer, the
        # incremental pipelined compute of the remaining prompt tokens
        # (the batch shares the network hops; experts each absorb a K/I
        # share of the FFN work in parallel).
        incr_layer = t_gateway + t_expert * K / n_exp
        extra_layer = (requests.prompt_len - 1).astype(np.float64) \
            * incr_layer                                          # (R,)

        if svc.per_satellite:
            # Batch-amortized gateway service (calibrated mode): estimate
            # each request's in-flight decode concurrency from the sorted
            # arrivals and the zero-load token latency, then read the
            # per-token decode service off the decode-attention roofline
            # at that batch size; a prefill amortizes the gateway weight
            # reads over its own prompt batch.
            dec_lat = np.where(self.nan_tok[:, R:], np.nan, token_lat[:, R:])
            with np.errstate(invalid="ignore"):
                mean_tok = float(np.nanmean(dec_lat)) if N else 0.0
            if not np.isfinite(mean_tok) or mean_tok <= 0.0:
                mean_tok = L * t_gateway
            dur = requests.decode_len.astype(np.float64) * mean_tok
            arr = requests.arrival_s.astype(np.float64)
            started = np.searchsorted(arr, arr, side="right")
            ended = np.searchsorted(np.sort(arr + dur), arr, side="right")
            conc = np.maximum(started - ended, 1)                 # (R,)
            self.decode_batch_est = conc
            pre_gw = requests.prompt_len.astype(np.float64) \
                * svc.gateway_s(ctx_len, batch=requests.prompt_len)
            dec_gw = svc.gateway_s(ctx_len, batch=conc)[tok_req]
            self.gw_service = np.concatenate([pre_gw, dec_gw])    # (M,)
        else:
            self.decode_batch_est = None
            self.gw_service = np.concatenate([
                requests.prompt_len.astype(np.float64) * t_gateway,
                np.full(N, t_gateway),
            ])                                                    # (M,)
        self.eff_layer = layer_lat.copy()                         # (P, M, L)
        self.eff_layer[:, :R, :] += extra_layer[None, :, None]
        self.tok_base = token_lat.copy()                          # (P, M)
        self.tok_base[:, :R] += L * extra_layer[None, :]
        self.start_pref = requests.arrival_s[None, :] \
            + self.ingress_extra                                  # (P, R)
        self.first_tok = np.cumsum(requests.decode_len) \
            - requests.decode_len                                 # (R,)

        # --- queue events: (plan, station, request, work) ------------------
        # Stations are satellites: each token's deposits land on the
        # satellites its slot's plan routes it through (the slot -> plan
        # gather), so colocated experts share their satellite's queue
        # (Eq. 43) and a mid-horizon plan switch redirects new deposits
        # while the old plan's backlog drains in place.
        self.gateways_slot = batch.gateways_by_slot()         # (P, N_T, L)
        self.expert_sats_slot = batch.expert_sats_by_slot()   # (P,N_T,L,I)
        eta_slot = batch.eta_by_slot()                        # (P, N_T)
        gw_tok = self.gateways_slot[:, self.slots]            # (P, M, L)
        sats_tok = self.expert_sats_slot[:, self.slots]       # (P, M, L, I)
        eta_tok = eta_slot[:, self.slots]                     # (P, M)

        # Gateway work: every token visits every gateway satellite of its
        # slot's plan; lm-head work on the last gateway.
        gw_station = gw_tok
        gw_work = np.broadcast_to(self.gw_service[None, :, None],
                                  (P, M, L)).copy()
        gw_work[:, :, L - 1] += t_head
        gw_req = np.concatenate([np.arange(R), tok_req])          # (M,)

        # Decode expert work: the engine's own draws, scattered onto the
        # drawn expert's satellite; colocation multiplies the deposited
        # work (the Eq. 43 q factor) and eta scales the shared-compute
        # efficiency.
        draws_mlk = np.moveaxis(draws, 0, 1)                      # (M, L, K)
        exp_sat_tok = np.take_along_axis(
            sats_tok, draws_mlk[None], axis=3)                    # (P,M,L,K)
        dec_exp_station = exp_sat_tok[:, R:]                      # (P,N,L,K)
        probs = activation.all_probs()                            # (L, I)
        if svc.per_satellite:
            # Calibrated deposits: each drawn expert's own service
            # seconds, scaled by the hosting satellite's speed — the
            # queue-theoretic face of the calibrated Eq. 43 term.
            exp_sec = np.asarray(svc.expert_s(), dtype=np.float64)  # (I,)
            inv_sp = np.asarray(svc.inv_speed(topo.n_sats),
                                dtype=np.float64)                 # (V,)
            dec_exp_work = (exp_sec[draws_mlk[R:]][None]
                            * inv_sp[dec_exp_station]
                            / eta_tok[:, R:, None, None])
            pre_exp_station = sats_tok[:, :R]                     # (P,R,L,I)
            pre_exp_work = (requests.prompt_len[None, :, None, None]
                            * probs[None, None, :, :]
                            * exp_sec[None, None, None, :]
                            * inv_sp[pre_exp_station]
                            / eta_tok[:, :R, None, None])
        else:
            dec_exp_work = np.broadcast_to(
                (t_expert / eta_tok[:, R:])[..., None, None],
                dec_exp_station.shape)

            # Prefill expert work: the whole prompt hits every expert of
            # the layer in proportion to its activation probability
            # (fluid split of the batch), deposited at the prefill
            # token's expert visit.
            pre_exp_station = sats_tok[:, :R]                     # (P,R,L,I)
            pre_exp_work = np.broadcast_to(
                requests.prompt_len[None, :, None, None]
                * probs[None, None, :, :] * t_expert
                / eta_tok[:, :R, None, None], (P, R, L, n_exp))

        ev_station = np.concatenate([
            gw_station.reshape(P, -1),
            dec_exp_station.reshape(P, -1),
            pre_exp_station.reshape(P, -1),
        ], axis=1)                                                # (P, E)
        ev_work = np.concatenate([
            gw_work.reshape(P, -1),
            dec_exp_work.reshape(P, -1),
            pre_exp_work.reshape(P, -1),
        ], axis=1)                                                # (P, E)
        ev_req = np.concatenate([
            np.broadcast_to(gw_req[:, None], (M, L)).ravel(),
            np.broadcast_to(tok_req[:, None, None], (N, L, K)).ravel(),
            np.broadcast_to(np.arange(R)[:, None, None],
                            (R, L, n_exp)).ravel(),
        ])                                                        # (E,)

        # Wait-gather stations: per (plan, token, layer) the gateway and
        # the K expert branches (max over branches joins the layer
        # critical path, mirroring the engine's max over experts).
        self.gather_gw_station = gw_station                       # (P, M, L)
        self.gather_exp_station = exp_sat_tok                     # (P,M,L,K)

        # Chunked service (continuous-batching semantics): a deposit
        # larger than one bin of capacity is spread over consecutive
        # bins at the service rate, so a long prefill does not
        # head-of-line-block every token behind one bin.  The chunk
        # layout depends only on work, so it is precomputed; per run
        # only the chunk *bins* are recomputed from the schedule.
        dt = qcfg.dt_s
        w_flat = ev_work.ravel()
        n_ch = np.maximum(np.ceil(w_flat / dt).astype(np.int64), 1)
        self._rep = np.repeat(np.arange(w_flat.size), n_ch)
        self._offs = np.arange(self._rep.size) \
            - np.repeat(np.cumsum(n_ch) - n_ch, n_ch)
        self.ev_chunk_work = np.minimum(w_flat[self._rep]
                                        - self._offs * dt, dt)
        self.ev_chunk_station = ev_station.ravel()[self._rep]
        self.ev_chunk_plan = np.broadcast_to(
            np.arange(P)[:, None], ev_work.shape).ravel()[self._rep]
        self.ev_chunk_req = np.broadcast_to(
            ev_req[None, :], ev_work.shape).ravel()[self._rep]
        self._n_events = ev_work.size

        # Fused-path gather indices: each chunk reads its event's arrival
        # time from the flattened [layer_arr | exp_arr] pair, so the
        # device fixed point rebuilds no event concatenations.  The block
        # order mirrors the ev_* concatenation above exactly.
        p_i = np.arange(P)[:, None, None]
        m_i = np.arange(M)[None, :, None]
        l_i = np.arange(L)[None, None, :]
        gw_src = (p_i * M + m_i) * L + l_i                        # (P, M, L)
        exp_src = P * M * L + gw_src                              # exp_arr
        ev_src = np.concatenate([
            gw_src.reshape(P, -1),
            np.broadcast_to(exp_src[:, R:, :, None],
                            (P, N, L, K)).reshape(P, -1),
            np.broadcast_to(exp_src[:, :R, :, None],
                            (P, R, L, n_exp)).reshape(P, -1),
        ], axis=1).ravel()
        self._chunk_src = ev_src[self._rep]
        self._chunk_row = self.ev_chunk_plan * self.n_stations \
            + self.ev_chunk_station
        self._chunk_pr = self.ev_chunk_plan * R + self.ev_chunk_req

        if batching is not None:
            # Continuous-batching chunk channels.  Decode-side events —
            # decode-token gateway visits and the decode expert block —
            # carry their work in ``wdec`` (the batchable subset the
            # speedup scales) and one fractional token visit per chunk
            # in ``cntw`` (a chunk holds work/ev_work of its event's
            # visit, so each decode event deposits exactly one occupancy
            # unit; a satellite hosting several layers of one token
            # counts that token once per visit).  Prefill blocks batch
            # over their own prompt already and count zero.
            ev_dec = np.concatenate([
                np.broadcast_to((np.arange(M) >= R)[:, None],
                                (M, L)).ravel(),
                np.ones(N * L * K, dtype=bool),
                np.zeros(R * L * n_exp, dtype=bool),
            ]).astype(np.float64)                                 # (E,)
            dec_ch = np.broadcast_to(ev_dec[None, :],
                                     ev_work.shape).ravel()[self._rep]
            wf = w_flat[self._rep]
            self._chunk_wdec = self.ev_chunk_work * dec_ch
            self._chunk_cntw = np.where(
                wf > 0.0,
                self.ev_chunk_work / np.where(wf > 0.0, wf, 1.0),
                0.0) * dec_ch
        #: Lazily-built device-resident precompute (see _device_tables).
        self._dev: dict | None = None
        #: Lazily-built joint-control-plane precompute (_ctrl_tables).
        self._ctrl: dict | None = None
        #: Deposit implementation: "auto" (Pallas on TPU, jnp scatter-add
        #: reference elsewhere), "segments" (row-bucketed segment_sum,
        #: bitwise-identical to "ref"), "ref", or "pallas".
        self.deposit_impl = "auto"

        # --- time bins (fixed across runs so the scan compiles once) ------
        start_dec0, _, c00 = self._chain(self.tok_base, self.start_pref)
        end0 = start_dec0 + self.tok_base[:, R:]
        horizon = max(float(requests.arrival_s.max()),
                      float(np.where(np.isfinite(end0), end0, 0.0).max()),
                      float(np.where(np.isfinite(c00), c00, 0.0).max()))
        self.n_bins = max(
            int(np.ceil((horizon + qcfg.tail_s) / qcfg.dt_s)) + 1,
            int(min_bins))
        if self.n_bins > 2_000_000:
            raise ValueError(
                f"{self.n_bins} time bins — raise dt_s or shrink the horizon")

        # --- migration background load (schedule switches) -----------------
        self._build_migration_load()

        # --- admission controller precompute ------------------------------
        acfg = qcfg.admission
        self.admission_on = acfg is not None \
            and acfg.policy in ("aimd", "pid")
        if self.admission_on:
            if acfg.policy == "pid" and acfg.gain_scale is not None \
                    and len(acfg.gain_scale) != len(self.schedules):
                raise ValueError(
                    f"gain_scale has {len(acfg.gain_scale)} entries for "
                    f"{len(self.schedules)} plans")
            self._build_admission_tables(acfg, ground, slot_r, rng)

        # --- fused-path row compaction + static tables --------------------
        self._build_row_map()
        self._build_fused_tables()

        # Filled by ``run``: (plan, satellite, bin) backlog of the last
        # fleet scan (the re-placement controller's observation).
        self.last_wait: np.ndarray | None = None
        # Telemetry: filled by every launch when ``probes`` is set.
        self.probes = probes
        self.last_probes: "ProbeRecord | None" = None

    # ----------------------------------------------------------------- #

    def _build_migration_load(self) -> None:
        """Precompute the background work a schedule's plan switches
        deposit on the fleet.

        Every slot boundary the wall-clock horizon crosses is checked
        against each row's :class:`~repro.core.schedule.PlanSchedule`;
        per moved expert (the ``distributed.elastic`` diff rule via
        :meth:`~repro.core.schedule.PlanSchedule.migrations_over`) the
        weight transfer occupies the *destination* satellite's queue for
        ``bytes * 8 / migration_rate_gbps`` seconds, chunked into dt
        bins from the boundary — arriving tokens queue behind the
        weights being installed.  Constant schedules deposit nothing, so
        the static path is untouched bit-for-bit.
        """
        qcfg = self.qcfg
        dt, T, S = qcfg.dt_s, self.n_bins, self.n_stations
        sec_per_expert = (qcfg.migration_bytes_per_expert * 8.0
                          / (qcfg.migration_rate_gbps * 1e9))
        flat_parts: list[np.ndarray] = []
        work_parts: list[np.ndarray] = []
        self.migration_bytes = np.zeros(self.n_plans)
        for p, sched in enumerate(self.schedules):
            for t_b, mig in sched.migrations_over(
                    T * dt, qcfg.slot_period_s,
                    qcfg.migration_bytes_per_expert):
                self.migration_bytes[p] += mig.bytes_moved
                if mig.n_moved == 0 or sec_per_expert <= 0.0:
                    continue
                n_ch = max(int(np.ceil(sec_per_expert / dt)), 1)
                bins = np.minimum(int(t_b / dt) + np.arange(n_ch), T - 1)
                w = np.minimum(sec_per_expert - np.arange(n_ch) * dt, dt)
                fl = ((p * S + mig.new_sats[:, None]) * T
                      + bins[None, :]).ravel()
                flat_parts.append(fl)
                work_parts.append(np.broadcast_to(
                    w[None, :], (mig.n_moved, n_ch)).ravel())
        self._mig_flat = (np.concatenate(flat_parts) if flat_parts
                          else np.empty(0, dtype=np.int64))
        self._mig_work = (np.concatenate(work_parts) if work_parts
                          else np.empty(0, dtype=np.float64))

    # ----------------------------------------------------------------- #

    def _build_admission_tables(self, acfg: AdmissionConfig,
                                ground: GroundSegment | None,
                                slot_r: np.ndarray,
                                rng: np.random.Generator) -> None:
        """Precompute the gateway-retry attempt tables and the AIMD
        controller's zero-load references.

        Per attempt a (0 = the original gateway, a >= 1 = the a-th best
        alternative gateway from :meth:`GroundSegment.retry_stations`):
        target gateway, total ingress latency (a * backoff + terrestrial
        forward + uplink + ingress hop) and per-plan feasibility.  An
        alternate gateway enters through the first rank of its
        ranked-visibility table whose ingress route exists for the plan
        in that slot (deeper ranks cover an occluded or unroutable best
        satellite).  When no a-th alternative exists — no ground
        segment, or fewer visible gateways than retries — attempt a is a
        same-gateway backoff retry: the origin is re-attempted after the
        backoff, drawing against the (time-varying) admit state of a
        later bin.  Retries happen within the arrival's topology slot
        (backoff << slot period).
        """
        req = self.requests
        P, R = self.n_plans, self.n_requests
        A = acfg.n_attempts
        self.n_gw_stations = ground.n_stations if ground is not None else 1

        # Without a ground segment there is a single logical gateway.
        station = req.station if ground is not None \
            else np.zeros(R, dtype=np.int64)
        st_att = np.tile(station, (A, 1))                         # (A, R)
        alt_ok = np.zeros((A, R), dtype=bool)
        alt_ok[0] = True
        if ground is not None and acfg.max_retries > 0:
            alts = ground.retry_stations(slot_r, req.station,
                                         acfg.max_retries)        # (R, n_alt)
            n_alt = alts.shape[1]
            for a in range(1, min(A, n_alt + 1)):
                st_att[a] = alts[:, a - 1]
                alt_ok[a] = True

        extra = np.empty((A, P, R))
        feas = np.zeros((A, P, R), dtype=bool)
        extra[0] = self.ingress_extra
        feas[0] = ~self.fail_ingress
        for a in range(1, A):
            if ground is None or not alt_ok[a].any():
                # Same-gateway backoff retry (see docstring).
                extra[a] = self.ingress_extra + a * acfg.retry_backoff_s
                feas[a] = feas[0]
                continue
            gdelay = ground.ground_delay_s[req.station, st_att[a]]
            # Ranked-visibility fallback: per plan, the first rank of
            # the alternate gateway's satellite ranking with a finite
            # ingress route.
            ing_r = ground.ingress_ranked[slot_r, st_att[a]]      # (R, K)
            up_r = ground.uplink_ranked_s[slot_r, st_att[a]]      # (R, K)
            best = np.zeros((P, R))
            best_ok = np.zeros((P, R), dtype=bool)
            for k in range(ground.n_ranked):
                reachable = ing_r[:, k] >= 0
                off = schedule_ingress_offsets(
                    self.batch, slot_r, np.where(reachable, ing_r[:, k], 0))
                ok = reachable[None, :] & np.isfinite(off)
                take = ok & ~best_ok
                best = np.where(take, up_r[None, :, k] + off, best)
                best_ok |= ok
            extra[a] = (a * acfg.retry_backoff_s + gdelay)[None, :] \
                + np.where(best_ok, best, 0.0)
            feas[a] = best_ok & alt_ok[a][None, :]
        self._att_station = st_att
        self._att_extra = extra
        self._att_feasible = feas
        # Attempt a is evaluated at the gateway it targets, after the
        # backoff + terrestrial forward but before the uplink.
        t_att = req.arrival_s[None, :] + np.arange(A)[:, None] \
            * acfg.retry_backoff_s
        if ground is not None:
            t_att = t_att + ground.ground_delay_s[req.station, st_att]
        self._att_bin = np.clip((t_att / self.qcfg.dt_s).astype(np.int64),
                                0, self.n_bins - 1)
        # Common random numbers: one uniform per (attempt, request),
        # shared by every plan and every run() call.
        self._adm_u = rng.random((A, R))

        # Zero-load controller references (see admission module
        # docstring): tail anchors at the configured reference quantile.
        base_ttft = self.ingress_extra + self.tok_base[:, :R]     # (P, R)
        ok = feas[0] & ~_segment_any(self.nan_tok[:, R:], self.tok_req, R) \
            & ~self.nan_tok[:, :R]
        self._adm_ttft0 = _station_quantile(
            base_ttft, ok, station, self.n_gw_stations,
            acfg.reference_quantile)                              # (P, G)
        dec_ok = np.isfinite(self.tok_base[:, R:]) & ~self.nan_tok[:, R:]
        self._adm_tpot0 = np.array([
            np.quantile(self.tok_base[i, R:][dec_ok[i]],
                        acfg.reference_quantile)
            if dec_ok[i].any() else 0.0 for i in range(P)])        # (P,)
        # Stashed for the fused control plane: the schedule row's
        # admission anchors are re-derived on device from exactly these
        # masked value tables (gathered per decided plan).
        self._adm_station = station
        self._adm_ok0 = ok
        self._adm_base_ttft = base_ttft
        self._adm_dec_ok = dec_ok

        # Slot-dependent critical-path stations for the in-scan
        # controller: per time bin, the bin's topology slot selects each
        # plan's gateway chain and expert satellites — the admission
        # law's qhat follows the schedule through every plan switch.
        slot_of_bin = slot_of_time(np.arange(self.n_bins) * self.qcfg.dt_s,
                                   self.qcfg.slot_period_s,
                                   self.n_topo_slots)
        self._adm_slot_of_bin = slot_of_bin
        self._adm_gw_idx = np.ascontiguousarray(np.moveaxis(
            self.gateways_slot[:, slot_of_bin], 1, 0)).astype(np.int32)
        self._adm_exp_idx = np.ascontiguousarray(np.moveaxis(
            self.expert_sats_slot[:, slot_of_bin], 1, 0)).reshape(
                self.n_bins, P, -1).astype(np.int32)

    # ----------------------------------------------------------------- #

    def _build_row_map(self) -> None:
        """Compact the (plan, satellite) queue rows the fused path keeps
        dense.

        Only rows that can ever receive a deposit (chunk targets,
        migration destinations) or be read (wait gathers, the admission
        law's per-bin station maps) matter; every other station carries
        exactly zero backlog in both paths, so dropping it from the
        device tensors is exact.  The map scales the fused kernel with
        the *plans'* footprint instead of the constellation size.
        """
        P, S, T = self.n_plans, self.n_stations, self.n_bins
        p_idx = np.arange(P)[:, None, None]
        gw_rows = p_idx * S + self.gather_gw_station              # (P,M,L)
        ex_rows = p_idx[..., None] * S + self.gather_exp_station
        used = [self._chunk_row, gw_rows.ravel(), ex_rows.ravel()]
        if self._mig_flat.size:
            used.append(self._mig_flat // T)
        if self.admission_on:
            pr = np.arange(P, dtype=np.int64)[None, :, None] * S
            used.append((pr + self._adm_gw_idx).ravel())
            used.append((pr + self._adm_exp_idx).ravel())
        rows = np.unique(np.concatenate(used))
        inv = np.full(P * S, -1, dtype=np.int64)
        inv[rows] = np.arange(rows.size)
        self._active_rows = rows
        self._row_inv = inv
        self.n_rows = int(rows.size)
        self._chunk_rowc = inv[self._chunk_row].astype(np.int32)
        self._gw_rowc = inv[gw_rows]                              # (P,M,L)
        self._ex_rowc = inv[ex_rows]                              # (P,M,L,K)
        if self.admission_on:
            self._adm_gw_rowc = inv[pr + self._adm_gw_idx] \
                .astype(np.int32)                                 # (T,P,L)
            self._adm_exp_rowc = inv[pr + self._adm_exp_idx] \
                .astype(np.int32)                                 # (T,P,LI)

    def _expand_rows(self, arr: np.ndarray) -> np.ndarray:
        """Scatter a compact-row array (..., n_rows) back to (..., P, S)."""
        full = np.zeros(arr.shape[:-1] + (self.n_plans * self.n_stations,),
                        dtype=arr.dtype)
        full[..., self._active_rows] = arr
        return full.reshape(arr.shape[:-1]
                            + (self.n_plans, self.n_stations))

    def _build_fused_tables(self) -> None:
        """Static precompute for the fused path's peeled first iteration
        and row-grouped deposits.

        The first fixed-point iteration always runs on the zero-wait
        schedule, so its event times — hence its chunk bins and gather
        bins — are construction-time constants; ``_launch`` turns them
        into the iteration-1 work plane with one host ``np.bincount``.
        The chunk tables are also re-ordered by compact row (stable
        sort), so the device scatter of later iterations walks the
        (row, T) plane row-major instead of hopping across it.
        """
        P, M, L = self.n_plans, self.n_tokens, self.n_layers
        z = np.zeros((P, M, L))
        layer0, exp0, *_ = self._schedule(z, z, self.start_pref)
        self._gw_b0, self._gw_fin0 = self._to_bins(layer0)
        self._ex_b0, self._ex_fin0 = self._to_bins(exp0)
        base0, fin0 = self._to_bins(self._event_times(layer0, exp0))
        bins0 = np.minimum(base0[self._rep] + self._offs, self.n_bins - 1)
        # Event-ordered copies (pre row-sort) — the joint control plane's
        # schedule-row chunk table is assembled in event order so the
        # per-(row, bin) f64 accumulation order matches a host-built
        # evaluation simulator exactly.
        self._chunk_bins0 = bins0
        self._chunk_fin0 = fin0[self._rep]
        perm = np.argsort(self._chunk_rowc, kind="stable")
        self._f_src = self._chunk_src[perm]
        self._f_offs = self._offs[perm]
        self._f_work = self.ev_chunk_work[perm]
        self._f_rowc = self._chunk_rowc[perm]
        self._f_pr = self._chunk_pr[perm]
        self._f_req = self.ev_chunk_req[perm]
        self._f_bins0 = bins0[perm]
        self._f_fin0 = fin0[self._rep][perm]
        if self.batching is not None:
            self._f_wdec = self._chunk_wdec[perm]
            self._f_cntw = self._chunk_cntw[perm]
        if self._mig_flat.size:
            flat = self._row_inv[self._mig_flat // self.n_bins] \
                * self.n_bins + self._mig_flat % self.n_bins
            self._mig_rm = np.bincount(
                flat, weights=self._mig_work,
                minlength=self.n_rows * self.n_bins
            ).reshape(self.n_rows, self.n_bins)
        else:
            self._mig_rm = None

    # ----------------------------------------------------------------- #

    def _chain(self, tok_total: np.ndarray, start_pref: np.ndarray):
        """Autoregressive chaining: (decode token starts (P, N), their
        per-request inclusive cumsums (P, N), prefill completion (P, R))."""
        R = self.n_requests
        dec = tok_total[:, R:]
        cs = np.cumsum(dec, axis=1)
        base = (cs - dec)[:, self.first_tok][:, self.tok_req]
        seg_excl = (cs - dec) - base
        c0 = start_pref + tok_total[:, :R]
        start_dec = c0[:, self.tok_req] + seg_excl
        return start_dec, cs - base, c0

    def _schedule(self, gw_wait: np.ndarray, ex_max: np.ndarray,
                  start_pref: np.ndarray):
        """Wait-augmented schedule: per-(plan, token, layer) gateway and
        expert arrival times, plus per-token total latencies."""
        lay_cost = self.eff_layer + gw_wait + ex_max              # (P, M, L)
        tok_total = self.tok_base + gw_wait.sum(2) + ex_max.sum(2)
        start_dec, seg_incl, c0 = self._chain(tok_total, start_pref)
        start_all = np.concatenate([start_pref, start_dec], axis=1)
        layer_arr = start_all[:, :, None] + _exclusive_cumsum(lay_cost, 2)
        exp_arr = layer_arr + gw_wait + self.gw_service[None, :, None]
        return layer_arr, exp_arr, tok_total, seg_incl, c0

    def _to_bins(self, times: np.ndarray):
        """Clip finite ``times`` to bin indices; returns (bins, finite)."""
        finite = np.isfinite(times)
        b = np.where(
            finite,
            np.clip((np.where(finite, times, 0.0) / self.qcfg.dt_s)
                    .astype(np.int64), 0, self.n_bins - 1), 0)
        return b, finite

    def _event_times(self, layer_arr: np.ndarray,
                     exp_arr: np.ndarray) -> np.ndarray:
        """(P*E,) arrival time of every queue event under a schedule."""
        P, R = self.n_plans, self.n_requests
        return np.concatenate([
            layer_arr.reshape(P, -1),
            np.broadcast_to(
                exp_arr[:, R:, :, None],
                (P, self.n_decode_tokens, self.n_layers,
                 self.activation.top_k)).reshape(P, -1),
            np.broadcast_to(
                exp_arr[:, :R, :, None],
                (P, R, self.n_layers, self.activation.n_experts))
            .reshape(P, -1),
        ], axis=1).ravel()

    def _bin_work(self, layer_arr, exp_arr, active2d):
        """Offered work (P, S, T) for the current schedule + per-plan
        request-activity mask ``active2d`` (P, R)."""
        P = self.n_plans
        S, T = self.n_stations, self.n_bins
        ev_time = self._event_times(layer_arr, exp_arr)           # (P*E,)
        base_bin, finite = self._to_bins(ev_time)
        bins = np.minimum(base_bin[self._rep] + self._offs, T - 1)
        w = self.ev_chunk_work * finite[self._rep] \
            * active2d[self.ev_chunk_plan, self.ev_chunk_req]
        flat = (self.ev_chunk_plan * S + self.ev_chunk_station) * T + bins
        if self._mig_flat.size:
            # Schedule-switch weight migrations ride as background load.
            flat = np.concatenate([flat, self._mig_flat])
            w = np.concatenate([w, self._mig_work])
        return np.bincount(flat, weights=w,
                           minlength=P * S * T).reshape(P, S, T)

    def _bin_work_planes(self, layer_arr, exp_arr, active2d):
        """Decode-work and occupancy-count planes (P, S, T) for the
        legacy path's continuous-batching law (:mod:`.batching`) —
        same bins as :meth:`_bin_work`, decode-side chunk channels,
        no migration background (weights are not batchable decode)."""
        P = self.n_plans
        S, T = self.n_stations, self.n_bins
        ev_time = self._event_times(layer_arr, exp_arr)           # (P*E,)
        base_bin, finite = self._to_bins(ev_time)
        bins = np.minimum(base_bin[self._rep] + self._offs, T - 1)
        act = finite[self._rep] \
            * active2d[self.ev_chunk_plan, self.ev_chunk_req]
        flat = (self.ev_chunk_plan * S + self.ev_chunk_station) * T + bins
        wdec = np.bincount(flat, weights=self._chunk_wdec * act,
                           minlength=P * S * T).reshape(P, S, T)
        cnt = np.bincount(flat, weights=self._chunk_cntw * act,
                          minlength=P * S * T).reshape(P, S, T)
        return wdec, cnt

    def _gather(self, wait, overload, layer_arr, exp_arr):
        """Per-(plan, token, layer) gateway wait, expert branch-max wait,
        and overload flags, read at the schedule's arrival bins."""
        p_idx = np.arange(self.n_plans)[:, None, None]
        gw_b, gw_fin = self._to_bins(layer_arr)
        gw_wait = np.where(gw_fin,
                           wait[p_idx, self.gather_gw_station, gw_b], 0.0)
        gw_over = gw_fin & overload[p_idx, self.gather_gw_station, gw_b]
        ex_b, ex_fin = self._to_bins(exp_arr)
        ex_b4, ex_f4 = ex_b[..., None], ex_fin[..., None]
        ex_wait = np.where(
            ex_f4, wait[p_idx[..., None], self.gather_exp_station, ex_b4],
            0.0)
        ex_over = ex_f4 & \
            overload[p_idx[..., None], self.gather_exp_station, ex_b4]
        return gw_wait, ex_wait.max(axis=3), gw_over, ex_over.any(axis=3)

    # ----------------------------------------------------------------- #

    def satellite_backlog(self, plan: int, t_s: float) -> np.ndarray:
        """(V,) seconds of backlog per satellite that plan row ``plan``
        observed at wall-clock ``t_s`` in the last ``run`` — the live
        signal the re-placement controller scores candidate plans
        against (zeros before any loaded run)."""
        if self.last_wait is None:
            return np.zeros(self.n_stations)
        b = min(int(t_s / self.qcfg.dt_s), self.n_bins - 1)
        return self.last_wait[plan, :, b]

    # ----------------------------------------------------------------- #

    def _device_tables(self) -> dict:
        """Build (once, lazily) the device-resident precompute pytree the
        fused fixed point consumes.

        Everything rate-independent is staged to the device in float64
        (x64 scoped to the transfer): the zero-load schedule tensors, the
        chunk layout + gather indices, the densified migration background
        load, and — when the AIMD controller is on — the admission scan
        tables and retry attempt tables.
        """
        if self._dev is not None:
            return self._dev
        qcfg = self.qcfg
        with jax.enable_x64():
            d = dict(
                dt=jnp.asarray(float(qcfg.dt_s)),
                cap32=jnp.asarray(float(qcfg.buffer_s), dtype=jnp.float32),
                dt32=jnp.asarray(float(qcfg.dt_s), dtype=jnp.float32),
                eff_layer=jnp.asarray(self.eff_layer),
                tok_base=jnp.asarray(self.tok_base),
                gw_service=jnp.asarray(self.gw_service),
                arrival_s=jnp.asarray(self.requests.arrival_s),
                ingress_extra0=jnp.asarray(self.ingress_extra),
                first_tok=jnp.asarray(self.first_tok),
                tok_req=jnp.asarray(self.tok_req),
                last_tok=jnp.asarray(
                    self.first_tok + self.requests.decode_len - 1),
                gw_rows=jnp.asarray(self._gw_rowc),
                ex_rows=jnp.asarray(self._ex_rowc),
                gw_b0=jnp.asarray(self._gw_b0),
                gw_fin0=jnp.asarray(self._gw_fin0),
                ex_b0=jnp.asarray(self._ex_b0),
                ex_fin0=jnp.asarray(self._ex_fin0),
            )
            if self._mig_rm is not None:
                d["mig_dense"] = jnp.asarray(self._mig_rm)    # (rows, T)
            if self.admission_on:
                acfg = qcfg.admission
                f32 = np.float32
                d.update(
                    ttft0=jnp.asarray(self._adm_ttft0.astype(f32)),
                    tpot0=jnp.asarray(self._adm_tpot0.astype(f32)),
                    ctrl=jnp.asarray(control_bin_flags(
                        self.n_bins, qcfg.dt_s, acfg.interval_s)),
                    gw_rows_bin=jnp.asarray(self._adm_gw_rowc),
                    exp_rows_bin=jnp.asarray(self._adm_exp_rowc),
                    increase=jnp.asarray(f32(acfg.increase)),
                    decrease=jnp.asarray(f32(acfg.decrease)),
                    admit_min=jnp.asarray(f32(acfg.admit_min)),
                    att_bin=jnp.asarray(self._att_bin),
                    att_station=jnp.asarray(self._att_station),
                    att_feasible=jnp.asarray(
                        np.moveaxis(self._att_feasible, 1, 0)),
                    att_extra=jnp.asarray(
                        np.moveaxis(self._att_extra, 0, 1)),
                    adm_u=jnp.asarray(self._adm_u),
                )
                if acfg.policy == "pid":
                    gain = np.ones(len(self.schedules)) \
                        if acfg.gain_scale is None \
                        else np.asarray(acfg.gain_scale, dtype=np.float64)
                    d.update(
                        pid_kp=jnp.asarray(f32(acfg.kp)),
                        pid_ki=jnp.asarray(f32(acfg.ki)),
                        pid_kd=jnp.asarray(f32(acfg.kd)),
                        pid_gain=jnp.asarray(gain.astype(f32)),
                    )
        self._dev = d
        return d

    def _deposit_mode(self) -> str:
        """Resolve the deposit implementation (see ``deposit_impl``).

        ``"auto"`` picks the Pallas one-hot-matmul kernel on TPU and the
        inline ``"ref"`` scatter everywhere else.  The ``"segments"``
        row-bucketed ``segment_sum`` path is bitwise identical to
        ``"ref"`` (so switching never moves a trace) and stays opt-in:
        ``bench_fleet``'s before/after stage timing shows it winning
        only on mid-size shuffled tables — the fleet's row-grouped
        chunk ordering keeps the inline scatter cache-friendly, and
        XLA:CPU's sort constants dominate beyond ~1M chunks.
        """
        if self.deposit_impl == "auto":
            return "pallas" if _kernel_ops.on_tpu() else "ref"
        if self.deposit_impl not in ("pallas", "segments", "ref"):
            raise ValueError(
                f"deposit_impl {self.deposit_impl!r} not in "
                "('auto', 'pallas', 'segments', 'ref')")
        return self.deposit_impl

    def _ctrl_tables(self) -> dict:
        """Host precompute for the joint control plane (lazy, cached).

        Everything here is independent of the controller configuration —
        the schedule row's compact station universe, the event-major
        gated chunk table, the decide walk's penalty row maps and the
        migration tables — so one cache serves every controller grid
        launched over this simulator.
        """
        if self._ctrl is not None:
            return self._ctrl
        qcfg = self.qcfg
        C, S, T = self.n_plans, self.n_stations, self.n_bins
        M, L, R = self.n_tokens, self.n_layers, self.n_requests
        N = self.n_decode_tokens
        K = self.activation.top_k
        dt, period = qcfg.dt_s, qcfg.slot_period_s
        n_slots = self.n_topo_slots

        # Schedule-row station universe: every satellite the schedule
        # row can deposit on, gather from, observe through the admission
        # maps or receive migrated weights at — the union over the
        # candidate pool (superset rows carry exactly-zero work, so the
        # compaction is exact, same argument as _build_row_map).
        gw_all = np.stack([np.asarray(p.gateways) for p in self.plans])
        ex_all = np.stack([np.asarray(p.expert_sats) for p in self.plans])
        used = [self.ev_chunk_station.ravel(), self.gather_gw_station.ravel(),
                self.gather_exp_station.ravel(), gw_all.ravel(),
                ex_all.ravel()]
        if self.admission_on:
            used += [self._adm_gw_idx.ravel(), self._adm_exp_idx.ravel()]
        srows = np.unique(np.concatenate(
            [np.asarray(u, dtype=np.int64) for u in used]))
        srow_inv = np.full(S, -1, dtype=np.int64)
        srow_inv[srows] = np.arange(srows.size)

        # Event-major gated chunk table: the probe's chunks re-sorted
        # (stable) by event, plan within event.  Only one plan's chunks
        # survive the slot gate per event, so the surviving deposits hit
        # each (row, bin) in event order — the accumulation order of a
        # host-built evaluation simulator's row-sorted bincount.
        E = self._n_events // C
        gw1 = np.arange(M)[:, None] * L + np.arange(L)[None, :]
        exp1 = M * L + gw1
        ev1 = np.concatenate([
            gw1.ravel(),
            np.broadcast_to(exp1[R:, :, None], (N, L, K)).ravel(),
            np.broadcast_to(exp1[:R, :, None],
                            (R, L, ex_all.shape[2])).ravel()])
        ev_local = self._rep % E
        perm = np.lexsort((self.ev_chunk_plan, ev_local))
        ct = dict(
            srows=srows, n_rows_sched=int(srows.size),
            ch_local=ev1[ev_local][perm],
            ch_work=self.ev_chunk_work[perm],
            ch_offs=self._offs[perm],
            ch_srow=srow_inv[self.ev_chunk_station[perm]].astype(np.int32),
            ch_plan=self.ev_chunk_plan[perm],
            ch_slot=self.slots[self.ev_chunk_req[perm]],
            ch_req=self.ev_chunk_req[perm],
            ch_bins0=self._chunk_bins0[perm],
            ch_fin0=self._chunk_fin0[perm].astype(np.float64),
        )

        # Decide-walk penalty row maps.  Round 1 reads the probe's
        # compact (plan, satellite) rows per incumbent (missing rows hit
        # the sentinel zero column — the host expansion reads 0.0
        # there); refinement rounds read the schedule row's universe.
        SR = self.n_rows
        pen1_gw = np.empty((C, C, L), dtype=np.int32)
        pen1_ex = np.empty((C, C) + ex_all.shape[1:], dtype=np.int32)
        for cur in range(C):
            rg = self._row_inv[cur * S + gw_all]
            pen1_gw[cur] = np.where(rg >= 0, rg, SR)
            re_ = self._row_inv[cur * S + ex_all]
            pen1_ex[cur] = np.where(re_ >= 0, re_, SR)
        ct["pen1_gw"] = pen1_gw
        ct["pen1_ex"] = pen1_ex
        ct["pen2_gw"] = srow_inv[gw_all].astype(np.int32)
        ct["pen2_ex"] = srow_inv[ex_all].astype(np.int32)

        # Schedule-row gather maps (stations -> compact schedule rows).
        ct["gw_srow"] = srow_inv[self.gather_gw_station].astype(np.int32)
        ct["ex_srow"] = srow_inv[self.gather_exp_station].astype(np.int32)

        # Decision-walk statics: the boundary count and per-boundary
        # backlog observation bin of replan.build_replan_schedule.
        horizon = T * dt
        n_bounds = min(int(np.floor(max(horizon, 0.0) / period)),
                       n_slots - 1)
        ct["n_bounds"] = n_bounds
        ct["decide_bins"] = tuple(
            min(int((k * period) / dt), T - 1) for k in range(n_bounds + 1))

        # Migration tables: all-pairs switch pricing (the decide gate)
        # plus the background-load deposit.  The deposit table holds
        # *sequential* repeated sums of the per-chunk occupancy — n
        # experts landing on one satellite deposit w added n times, not
        # n * w, exactly the host bincount's accumulation.
        n_moved, dest = migration_matrix(self.plans, 1.0, S)
        ct["n_moved"] = n_moved
        sec = (qcfg.migration_bytes_per_expert * 8.0
               / (qcfg.migration_rate_gbps * 1e9))
        if sec > 0.0:
            n_chm = max(int(np.ceil(sec / dt)), 1)
            w_prof = np.minimum(sec - np.arange(n_chm) * dt, dt)
        else:
            w_prof = np.zeros(0)
        max_cnt = int(dest.max())
        rep = np.zeros((len(w_prof), max_cnt + 1))
        for j, w in enumerate(w_prof):
            for n in range(1, max_cnt + 1):
                rep[j, n] = rep[j, n - 1] + w
        ct["n_mig_chunks"] = int(len(w_prof))
        ct["mig_plane"] = rep[:, dest[:, :, srows].astype(np.int64)]
        nbm = int(np.floor(horizon / period))
        ct["mig_bounds"] = tuple(
            (int((k - 1) % n_slots), int(k % n_slots),
             int((k * period) / dt)) for k in range(1, nbm + 1))

        if self.admission_on:
            # Masked admission-anchor inputs for the schedule row's
            # on-device quantiles + per-bin station maps.
            ct["adm_ok0"] = self._adm_ok0
            ct["adm_base_ttft"] = self._adm_base_ttft
            ct["adm_station"] = self._adm_station
            ct["adm_dec_ok"] = self._adm_dec_ok
            ct["adm_dec_vals"] = self.tok_base[:, R:]
            ct["att_feas_c"] = np.moveaxis(self._att_feasible, 1, 0)
            ct["att_extra_c"] = np.moveaxis(self._att_extra, 0, 1)
            ct["gw_srow_bin"] = srow_inv[self._adm_gw_idx].astype(np.int32)
            ct["exp_srow_bin"] = srow_inv[self._adm_exp_idx].astype(np.int32)
            ct["slot_of_bin"] = self._adm_slot_of_bin
        self._ctrl = ct
        return ct

    def _launch(self, masks: np.ndarray, ttft_targets, tpot_targets,
                want_wait: bool) -> dict:
        """One fused device launch over the leading sweep axis F.

        The request-activity masks are folded into a host-built compacted
        chunk table (only active chunks are deposited; padded to
        ``_CHUNK_BLOCK`` so repeated sweeps of the same shape reuse the
        compile cache) — the device sees offered work, not the envelope.

        Args:
            masks: (F, R) bool request-activity masks.
            ttft_targets: Optional (F,) raw TTFT targets (margin applied
                here); None uses the construction-time config.
            tpot_targets: Same for TPOT.
            want_wait: Return the (T, F, rows) backlog trace.

        Returns:
            The :func:`_fused_core` output dict as host arrays, each
            with a leading F axis (``wait`` stays time-major compact).
        """
        acfg = self.qcfg.admission
        F = masks.shape[0]
        if self.admission_on:
            m = acfg.target_margin
            tt = (np.full(F, m * acfg.ttft_target_s) if ttft_targets is None
                  else m * np.asarray(ttft_targets, dtype=np.float64))
            tp = (np.full(F, m * acfg.tpot_target_s) if tpot_targets is None
                  else m * np.asarray(tpot_targets, dtype=np.float64))
        else:
            tt = np.zeros(F)
            tp = np.zeros(F)

        # Host-side chunk compaction: keep (f, chunk) pairs whose
        # request is active, in the static row-grouped order.  Padding
        # rides along with zero work.  The compaction streams one sweep
        # row at a time — peak host memory is O(n_chunks + active), not
        # the O(F * n_chunks) dense activity matrix a 2-D np.nonzero
        # would materialize — with the concatenation preserving the
        # f-major, chunk-ascending order bit-for-bit.
        P, R = self.n_plans, self.n_requests
        T, SR = self.n_bins, self.n_rows
        cids = [np.flatnonzero(masks[f, self._f_req]) for f in range(F)]
        f_id = np.repeat(np.arange(F),
                         np.array([c.size for c in cids], dtype=np.int64))
        cid = (np.concatenate(cids) if cids
               else np.empty(0, dtype=np.int64))
        n = cid.size
        n_pad = max(-(-n // _CHUNK_BLOCK), 1) * _CHUNK_BLOCK
        pml2 = 2 * P * self.n_tokens * self.n_layers
        src = np.zeros(n_pad, dtype=np.int64)
        src[:n] = f_id * pml2 + self._f_src[cid]
        offs = np.zeros(n_pad, dtype=np.int64)
        offs[:n] = self._f_offs[cid]
        work = np.zeros(n_pad)
        work[:n] = self._f_work[cid]
        fprow = np.zeros(n_pad, dtype=np.int32)
        fprow[:n] = f_id.astype(np.int32) * SR + self._f_rowc[cid]
        chunks = dict(src=src, offs=offs, work=work, fprow=fprow)
        if self.admission_on:
            fpr = np.zeros(n_pad, dtype=np.int64)
            fpr[:n] = f_id * (P * R) + self._f_pr[cid]
            chunks["fpr"] = fpr
        if self.batching is not None:
            wdec = np.zeros(n_pad)
            wdec[:n] = self._f_wdec[cid]
            cntw = np.zeros(n_pad)
            cntw[:n] = self._f_cntw[cid]
            chunks["wdec"] = wdec
            chunks["cntw"] = cntw

        # Iteration-1 offered work: the zero-wait schedule's bins are
        # static, so one host bincount over the active chunks builds the
        # peeled iteration's plane (a launch input, not a per-iteration
        # transfer).
        flat0 = (f_id * SR + self._f_rowc[cid]).astype(np.int64) * T \
            + self._f_bins0[cid]
        # astype: bincount of an *empty* chunk set (an all-False sweep
        # row) returns int64 even with weights given.
        plane0 = np.bincount(
            flat0, weights=self._f_work[cid] * self._f_fin0[cid],
            minlength=F * SR * T).reshape(F, SR, T).astype(np.float64)
        if self._mig_rm is not None:
            plane0 += self._mig_rm[None]
        work0_sum = plane0.sum(axis=2)                        # (F, SR)
        beff0 = None
        if self.batching is not None:
            # The peeled iteration's effective work is host-computed in
            # f64 (mirroring the device's f64-scatter-then-f32-downcast
            # policy) from the decode-work and occupancy planes of the
            # same static bins.
            plane0_dec = np.bincount(
                flat0, weights=self._f_wdec[cid] * self._f_fin0[cid],
                minlength=F * SR * T).reshape(F, SR, T)
            cnt0 = np.bincount(
                flat0, weights=self._f_cntw[cid] * self._f_fin0[cid],
                minlength=F * SR * T).reshape(F, SR, T)
            plane0, beff0 = effective_work_np(
                plane0, plane0_dec, cnt0, self._batch_table,
                self._batch_cap, self._batch_window)

        # Telemetry rings: static (capacity, stride) pair + donated
        # zeroed buffers.  probes=None launches pass an empty pytree and
        # trace exactly the legacy kernel.
        if self.probes is not None:
            p_cap, p_stride = self.probes.resolve(self.n_bins)
            static_probes = (p_cap, p_stride)
            n_gw = self._adm_ttft0.shape[1] if self.admission_on else 0
            pbuf = {k: jnp.asarray(v) for k, v in make_buffers(
                p_cap, F, SR,
                (P, n_gw) if self.admission_on else None,
                n_row_channels=4 if self.batching is not None else 3
            ).items()}
            exec_fn = _fused_exec_probed
        else:
            static_probes = None
            pbuf = {}
            exec_fn = _fused_exec
        # Batching pytree: empty when off (the trace then shares the
        # batching-free compile-cache entry); the host-computed beff0
        # ships only for the probed n_iter == 1 peel, which has no
        # device-side occupancy plane to record from.
        batch_np: dict = {}
        batch_window = 0
        if self.batching is not None:
            batch_np = dict(table=self._batch_table,
                            bcap=np.float64(self._batch_cap))
            batch_window = self._batch_window
            if self.probes is not None and max(1, self.qcfg.iterations) == 1:
                batch_np["beff0"] = beff0.astype(np.float32)
        with jax.enable_x64(), warnings.catch_warnings():
            # CPU jit declines buffer donation with a UserWarning; the
            # request is still the right thing on TPU/GPU.
            warnings.filterwarnings("ignore", message=".*[Dd]onat")
            out = exec_fn(
                self._device_tables(),
                {k: jnp.asarray(v) for k, v in chunks.items()},
                jnp.asarray(plane0.astype(np.float32)),
                jnp.asarray(work0_sum),
                jnp.asarray(tt), jnp.asarray(tp), pbuf,
                {k: jnp.asarray(v) for k, v in batch_np.items()},
                max(1, self.qcfg.iterations), self.n_bins, self.n_rows,
                self.admission_on, self._deposit_mode(), want_wait,
                static_probes, batch_window)
            out = {k: jax.tree_util.tree_map(np.asarray, v)
                   for k, v in out.items()}
        if self.probes is not None:
            # Probe outputs have their own leading axes — ingest and pop
            # them here so run/run_many's per-F slicing stays untouched.
            self.last_probes = ProbeRecord.from_launch(
                out.pop("probes"), out.pop("probe_gw_wait"),
                out.pop("probe_ex_wait"), self.qcfg.dt_s, p_cap, p_stride,
                self.n_bins, self._expand_rows)
        return out

    def run(self, active: np.ndarray | None = None,
            zero_load: bool = False,
            kv_slots: int | None = None, *,
            replan=None, replan_rng=None):
        """Simulate with an optional per-request activity mask (Poisson
        thinning for rate sweeps) and return per-plan traffic metrics.

        The fixed point executes as **one fused device launch** (see
        :func:`_fused_core`); :meth:`run_legacy` is the host-path anchor
        it is pinned against.  ``zero_load`` delegates to the host path
        (the queue scan is skipped entirely there, so the zero-load
        reference stays bitwise equal to the engine).

        Args:
            active: Optional (R,) bool participation mask (default: all).
            zero_load: Skip queueing and admission entirely.
            kv_slots: Optional override of the static KV admission cap
                (the cap is host post-processing, so budget sweeps reuse
                one device launch shape).
            replan: Optional ``repro.traffic.replan.ReplanConfig`` —
                runs the **joint control plane** instead: probe, the
                re-placement decide walk and the decided schedule's
                evaluation execute as one device launch
                (:func:`_ctrl_core`), and the return value becomes a
                ``ReplanOutcome`` (parity anchor:
                ``replan_traffic``).  Composes with no other option.
            replan_rng: RNG for the controller's base candidate scores
                (``replan`` only; default ``np.random.default_rng(0)``).

        Returns:
            A :class:`~repro.traffic.metrics.TrafficResult` with one
            :class:`~repro.traffic.metrics.PlanTraffic` per plan — or a
            ``ReplanOutcome`` when ``replan`` is given.
        """
        if replan is not None:
            if active is not None or zero_load or kv_slots is not None:
                raise ValueError(
                    "run(replan=...) composes with no other run() option")
            from .replan import replan_base_scores
            rng = (np.random.default_rng(0) if replan_rng is None
                   else replan_rng)
            scores = replan_base_scores(
                self.plans, self.topo, self.activation, self.workload,
                self.compute, rng, replan)
            return self.run_replan_grid(replan, base_scores=scores)[0]
        if zero_load:
            return self.run_legacy(active, zero_load=True,
                                   kv_slots=kv_slots)
        if active is None:
            active = np.ones(self.n_requests, dtype=bool)
        active = np.asarray(active, dtype=bool)
        out = self._launch(active[None, :], None, None, want_wait=True)
        # Exposed for the re-placement controller: the live
        # (plan, satellite, bin) backlog of the last fleet scan,
        # expanded from compact rows back to every satellite.
        wait = out.pop("wait")                       # (T, 1, rows)
        self.last_wait = np.moveaxis(
            self._expand_rows(wait[:, 0, :]), 0, 2)  # (P, S, T)
        out = {k: v[0] for k, v in out.items()}
        out["work_sum"] = self._expand_rows(out["work_sum"])
        return self._finalize(active, out, self.admission_on, kv_slots)

    def run_many(self, active: np.ndarray | None = None, *,
                 ttft_targets: np.ndarray | None = None,
                 tpot_targets: np.ndarray | None = None,
                 kv_slots: int | None = None,
                 replan=None, replan_rng=None, base_scores=None,
                 cadences=None, mig_weights=None) -> list:
        """Run a whole sweep as one compile + one device launch.

        The F sweep entries ride a vmapped leading axis of the fused
        fixed point: a saturation sweep batches thinning masks, the
        admission-frontier benchmark batches latency targets — either
        way the fused kernel is traced once (``FUSED_TRACE_COUNT``) and
        the per-entry results come back from a single launch.

        With ``replan`` given the sweep becomes a **controller grid**:
        cadence x migration-budget x admission-target cells batch the
        leading axis of one joint-control-plane launch
        (:meth:`run_replan_grid`) and the return value is one
        ``ReplanOutcome`` per cell.

        Args:
            active: (F, R) bool participation masks (one row per sweep
                entry; rows may repeat when only targets vary).  Must be
                None when ``replan`` is given (the controller grid is
                always all-active).
            ttft_targets: Optional (F,) TTFT targets overriding the
                construction-time admission config (AIMD runs only).
                Under ``replan``: the admission-target grid axis.
            tpot_targets: Optional (F,) TPOT targets, same contract.
            kv_slots: Optional static-cap override (host post-processing).
            replan: Optional ``ReplanConfig`` switching to the joint
                control plane.
            replan_rng: RNG for the controller's base candidate scores
                (used when ``base_scores`` is None).
            base_scores: Optional precomputed (n_slots, C) base score
                table (``replan_base_scores``).
            cadences: Optional replan-cadence grid axis (slots between
                decisions; default: the config's ``period_slots``).
            mig_weights: Optional migration-budget grid axis (s/MB
                switch pricing; default the config's weight).

        Returns:
            One :class:`~repro.traffic.metrics.TrafficResult` per sweep
            entry, in order — or one ``ReplanOutcome`` per grid cell
            (cadence-major, then migration weight, then target) when
            ``replan`` is given.
        """
        if replan is not None:
            if active is not None or kv_slots is not None:
                raise ValueError(
                    "run_many(replan=...) composes only with the "
                    "target/cadence/migration grid axes")
            if base_scores is None:
                from .replan import replan_base_scores
                rng = (np.random.default_rng(0) if replan_rng is None
                       else replan_rng)
                base_scores = replan_base_scores(
                    self.plans, self.topo, self.activation, self.workload,
                    self.compute, rng, replan)
            return self.run_replan_grid(
                replan, base_scores=base_scores, cadences=cadences,
                mig_weights=mig_weights, ttft_targets=ttft_targets,
                tpot_targets=tpot_targets)
        if cadences is not None or mig_weights is not None \
                or base_scores is not None:
            raise ValueError("controller grid axes need replan=...")
        if active is None:
            raise ValueError("run_many needs (F, R) activity masks")
        masks = np.asarray(active, dtype=bool)
        if masks.ndim != 2 or masks.shape[1] != self.n_requests:
            raise ValueError(f"active must be (F, {self.n_requests})")
        if (ttft_targets is not None or tpot_targets is not None) \
                and not self.admission_on:
            raise ValueError(
                "latency-target sweeps need an AIMD admission config")
        out = self._launch(masks, ttft_targets, tpot_targets,
                           want_wait=False)
        out["work_sum"] = self._expand_rows(out["work_sum"])
        return [
            self._finalize(masks[f], {k: v[f] for k, v in out.items()},
                           self.admission_on, kv_slots)
            for f in range(masks.shape[0])
        ]

    def run_replan_grid(self, rcfg, *, base_scores,
                        cadences=None, mig_weights=None,
                        ttft_targets=None, tpot_targets=None) -> list:
        """One joint-control-plane launch over a controller grid.

        Probe, decide walk and schedule-row evaluation execute inside a
        single device program (:func:`_ctrl_core`), batched over the
        grid's leading axis — F = cadences x migration weights x
        admission targets, cell order cadence-major.  The host
        controller (``repro.traffic.replan.replan_traffic``) stays the
        semantic anchor; on CPU the fused controller reproduces its
        switch decisions and served/shed sets bit for bit.  Paths where
        the host controller remains authoritative raise here:
        continuous batching, probe rings, calibrated per-satellite
        service (its decode-batch estimate depends on the evaluated
        plan pool) and candidate pools that already contain schedules.

        Args:
            rcfg: ``ReplanConfig`` (mode/hysteresis/pricing; its
                ``period_slots`` / ``migration_weight_s_per_mb`` seed
                the grid axes when none are given).
            base_scores: (n_topo_slots, C) backlog-free candidate
                scores per slot (``replan_base_scores``) — the decide
                law adds the backlog penalty on device.
            cadences: Iterable of decision cadences in slots (>= 1).
            mig_weights: Iterable of migration prices (s/MB, >= 0).
            ttft_targets: Optional admission-target axis (raw seconds,
                zipped with ``tpot_targets``; admission runs only).
            tpot_targets: Optional TPOT targets (zips with
                ``ttft_targets``).

        Returns:
            One ``ReplanOutcome`` per grid cell: last-round decisions,
            the stitched candidates+schedule ``TrafficResult``, the
            probe result (backlog mode) and this simulator as ``sim``.
        """
        from .replan import (REPLAN_MODES, ReplanDecision, ReplanOutcome,
                             ReplanReport)

        qcfg = self.qcfg
        acfg = qcfg.admission
        if rcfg.mode not in REPLAN_MODES:
            raise ValueError(f"unknown replan mode: {rcfg.mode!r}")
        if self.batching is not None:
            raise NotImplementedError(
                "joint control plane: continuous batching stays on the "
                "host controller (replan_traffic)")
        if self.probes is not None:
            raise NotImplementedError(
                "joint control plane: probe rings are not recorded on "
                "the control launch — use replan_traffic for probed "
                "rounds")
        if self.service_model.per_satellite:
            raise NotImplementedError(
                "joint control plane: calibrated per-satellite service "
                "recomputes its decode-batch estimate per evaluated "
                "plan pool — the host controller is authoritative")
        if any(not s.is_constant for s in self.schedules):
            raise ValueError(
                "run_replan_grid needs a static candidate pool (plain "
                "plans); schedules cannot be re-decided")
        if (ttft_targets is not None or tpot_targets is not None) \
                and not self.admission_on:
            raise ValueError(
                "admission-target axes need an admission config")
        if self.admission_on and getattr(acfg, "gain_scale", None) \
                is not None:
            raise NotImplementedError(
                "joint control plane: per-plan admission gains are "
                "pool-indexed and do not transfer to the decided "
                "schedule row")

        C = self.n_plans
        n_slots = self.n_topo_slots
        T, R, M = self.n_bins, self.n_requests, self.n_tokens
        bs = np.asarray(base_scores, dtype=np.float64)
        if bs.shape != (n_slots, C):
            raise ValueError(f"base_scores must be ({n_slots}, {C})")

        cads = ([int(rcfg.period_slots)] if cadences is None
                else [int(c) for c in cadences])
        migw = ([float(rcfg.migration_weight_s_per_mb)]
                if mig_weights is None
                else [float(w) for w in mig_weights])
        if any(c < 1 for c in cads):
            raise ValueError("cadences must be >= 1")
        if any(w < 0 for w in migw):
            raise ValueError("migration weights must be >= 0")
        tts = [None] if ttft_targets is None else list(ttft_targets)
        tps = [None] * len(tts) if tpot_targets is None \
            else list(tpot_targets)
        if len(tps) != len(tts):
            raise ValueError("ttft_targets and tpot_targets must zip")
        cells = [(c, w, i) for c in cads for w in migw
                 for i in range(len(tts))]
        F = len(cells)

        if self.admission_on:
            m = acfg.target_margin
            tt = np.array([m * (acfg.ttft_target_s if tts[i] is None
                                else tts[i]) for _, _, i in cells])
            tp = np.array([m * (acfg.tpot_target_s if tps[i] is None
                                else tps[i]) for _, _, i in cells])
        else:
            tt, tp = np.zeros(F), np.zeros(F)

        ct = self._ctrl_tables()
        K1 = ct["n_bounds"] + 1
        dmask = np.zeros((F, K1), dtype=bool)
        for f, (cad, _w, _i) in enumerate(cells):
            for k in range(K1):
                dmask[f, k] = (k == 0) or (rcfg.mode != "off"
                                           and k % cad == 0)
        bpe = (qcfg.migration_bytes_per_expert
               if rcfg.bytes_per_expert is None else rcfg.bytes_per_expert)
        cc = dict(
            base_scores=bs[np.arange(K1) % n_slots],
            decide_mask=dmask,
            mig_w=np.array([w for _, w, _ in cells]),
            bytes_mat=ct["n_moved"] * bpe,
            pen1_gw=ct["pen1_gw"], pen1_ex=ct["pen1_ex"],
            pen2_gw=ct["pen2_gw"], pen2_ex=ct["pen2_ex"],
            slot_tok=self.slots,
            gw_srow=ct["gw_srow"], ex_srow=ct["ex_srow"],
            ch_local=ct["ch_local"], ch_work=ct["ch_work"],
            ch_offs=ct["ch_offs"], ch_srow=ct["ch_srow"],
            ch_plan=ct["ch_plan"], ch_slot=ct["ch_slot"],
            ch_bins0=ct["ch_bins0"], ch_fin0=ct["ch_fin0"],
        )
        if ct["n_mig_chunks"] and ct["mig_bounds"]:
            cc["mig_plane"] = ct["mig_plane"]
        if self.admission_on:
            cc.update(
                ch_req=ct["ch_req"], adm_ok0=ct["adm_ok0"],
                adm_base_ttft=ct["adm_base_ttft"],
                adm_station=ct["adm_station"],
                adm_dec_ok=ct["adm_dec_ok"],
                adm_dec_vals=ct["adm_dec_vals"],
                att_feas_c=ct["att_feas_c"],
                att_extra_c=ct["att_extra_c"],
                gw_srow_bin=ct["gw_srow_bin"],
                exp_srow_bin=ct["exp_srow_bin"],
                slot_of_bin=ct["slot_of_bin"])
        n_rounds = (max(1, int(rcfg.controller_iterations))
                    if rcfg.mode == "backlog" else 1)
        meta = _CtrlMeta(
            n_iter=max(1, qcfg.iterations), n_bins=T,
            n_rows=self.n_rows, n_rows_sched=ct["n_rows_sched"],
            n_cand=C, n_slots=n_slots, n_bounds=ct["n_bounds"],
            n_rounds=n_rounds, adm_on=self.admission_on,
            deposit_mode=self._deposit_mode(),
            mode_backlog=(rcfg.mode == "backlog"),
            hysteresis=float(rcfg.hysteresis),
            ref_q=(float(acfg.reference_quantile)
                   if self.admission_on else 0.0),
            decide_bins=ct["decide_bins"],
            n_mig_chunks=ct["n_mig_chunks"],
            mig_bounds=ct["mig_bounds"])

        # Probe chunk table: the all-active compaction of _launch (every
        # grid cell offers the full request set).  The probe fixed point
        # depends on the admission (TTFT, TPOT) target alone — not on
        # cadence or migration budget — so the table is built at the
        # deduplicated admission-cell width Fu and the device gathers
        # the probe back to F (``probe_gather``).  A grid whose cells
        # share one admission target (e.g. a cadence x budget sweep)
        # runs the probe exactly once.
        uniq, inv = np.unique(np.stack([tt, tp], axis=1), axis=0,
                              return_inverse=True)
        Fu = uniq.shape[0]
        cc["probe_ttft"] = uniq[:, 0]
        cc["probe_tpot"] = uniq[:, 1]
        cc["probe_gather"] = inv.astype(np.int64).reshape(F)
        P, SR = self.n_plans, self.n_rows
        nch = self._f_work.size
        f_id = np.repeat(np.arange(Fu), nch)
        cid = np.tile(np.arange(nch), Fu)
        n = cid.size
        n_pad = max(-(-n // _CHUNK_BLOCK), 1) * _CHUNK_BLOCK
        pml2 = 2 * P * M * self.n_layers
        src = np.zeros(n_pad, dtype=np.int64)
        src[:n] = f_id * pml2 + self._f_src[cid]
        offs = np.zeros(n_pad, dtype=np.int64)
        offs[:n] = self._f_offs[cid]
        work = np.zeros(n_pad)
        work[:n] = self._f_work[cid]
        fprow = np.zeros(n_pad, dtype=np.int32)
        fprow[:n] = f_id.astype(np.int32) * SR + self._f_rowc[cid]
        chunks = dict(src=src, offs=offs, work=work, fprow=fprow)
        if self.admission_on:
            fpr = np.zeros(n_pad, dtype=np.int64)
            fpr[:n] = f_id * (P * R) + self._f_pr[cid]
            chunks["fpr"] = fpr
        flat0 = (f_id * SR + self._f_rowc[cid]).astype(np.int64) * T \
            + self._f_bins0[cid]
        plane0 = np.bincount(
            flat0, weights=self._f_work[cid] * self._f_fin0[cid],
            minlength=Fu * SR * T).reshape(Fu, SR, T).astype(np.float64)
        if self._mig_rm is not None:
            plane0 += self._mig_rm[None]

        with jax.enable_x64():
            out = _ctrl_exec(
                self._device_tables(),
                {k: jnp.asarray(v) for k, v in chunks.items()},
                jnp.asarray(plane0.astype(np.float32)),
                jnp.asarray(plane0.sum(axis=2)),
                jnp.asarray(tt), jnp.asarray(tp),
                {k: jnp.asarray(v) for k, v in cc.items()}, meta)
            out = jax.tree_util.tree_map(np.asarray, out)

        sp_all, telem = out["slot_plan"], out["telem"]
        probe_o, sched_o = out["probe"], out["sched"]
        srows = ct["srows"]

        def expand_srows(a):
            full = np.zeros(a.shape[:-1] + (self.n_stations,), a.dtype)
            full[..., srows] = a
            return full

        names = list(self.batch.names)
        outcomes = []
        for f in range(F):
            schedule = PlanSchedule(plans=self.plans, slot_plan=sp_all[f],
                                    name=f"replan/{rcfg.mode}")
            decisions = [
                ReplanDecision(
                    boundary=k, slot=k % n_slots,
                    chosen=int(telem["chosen"][f, k]),
                    switched=bool(telem["switched"][f, k]),
                    scores=telem["scores"][f, k].copy(),
                    migration_bytes=float(telem["mig_bytes"][f, k]))
                for k in range(K1) if dmask[f, k]
            ]
            # Decision-event channel: the decide loop's device telemetry
            # at this cell's decide boundaries, export-ready.
            dk = np.flatnonzero(dmask[f])
            trace = DecisionTrace(
                period_s=float(qcfg.slot_period_s),
                boundaries=dk.astype(np.int64),
                slots=(dk % n_slots).astype(np.int64),
                scores=telem["scores"][f, dk].astype(np.float64),
                chosen=telem["chosen"][f, dk].astype(np.int64),
                switched=telem["switched"][f, dk].astype(bool),
                migration_bytes=telem["mig_bytes"][f, dk]
                .astype(np.float64))
            report = ReplanReport(schedule=schedule, decisions=decisions,
                                  candidates=list(self.plans),
                                  trace=trace)
            probe_res = None
            if rcfg.mode == "backlog":
                po = {k2: v[f] for k2, v in probe_o.items()}
                po["work_sum"] = self._expand_rows(po["work_sum"])
                probe_res = self._finalize(np.ones(R, dtype=bool), po,
                                           self.admission_on)
            stitched = {
                k2: np.concatenate([probe_o[k2][f], sched_o[k2][f]],
                                   axis=0)
                for k2 in ("ttft", "e2e", "tok_total", "tok_over",
                           "shed", "retries")}
            stitched["work_sum"] = np.concatenate(
                [self._expand_rows(probe_o["work_sum"][f]),
                 expand_srows(sched_o["work_sum"][f])[None]], axis=0)
            plan_tok = sp_all[f][self.slots]
            billed = float(sum(
                mg.bytes_moved for _, mg in schedule.migrations_over(
                    T * qcfg.dt_s, qcfg.slot_period_s,
                    qcfg.migration_bytes_per_expert)))
            res = self._finalize(
                np.ones(R, dtype=bool), stitched, self.admission_on,
                names=names + [schedule.name],
                nan_tok=np.concatenate(
                    [self.nan_tok,
                     self.nan_tok[plan_tok, np.arange(M)][None]]),
                fail_ingress=np.concatenate(
                    [self.fail_ingress,
                     self.fail_ingress[plan_tok[:R],
                                       np.arange(R)][None]]),
                migration_bytes=np.append(self.migration_bytes, billed))
            outcomes.append(ReplanOutcome(report=report, result=res,
                                          probe=probe_res, sim=self))
        return outcomes

    def run_legacy(self, active: np.ndarray | None = None,
                   zero_load: bool = False,
                   kv_slots: int | None = None) -> TrafficResult:
        """Host-path reference fixed point (the pre-fusion ``run``).

        Iterates schedule -> bin -> scan -> gather with the schedule,
        binning and gather steps on the host and only the backlog scan
        on device (whose inputs downcast to float32, as they always
        have — the fused path reproduces exactly that downcast) — the
        authoritative semantic anchor the fused path is parity-pinned
        against in ``tests/test_fleet_perf.py``.

        Args:
            active: Optional (R,) bool participation mask (default: all).
            zero_load: Skip queueing and admission entirely.
            kv_slots: Optional override of the static KV admission cap.

        Returns:
            A :class:`~repro.traffic.metrics.TrafficResult` with one
            :class:`~repro.traffic.metrics.PlanTraffic` per plan.
        """
        qcfg = self.qcfg
        acfg = qcfg.admission
        req = self.requests
        P, R = self.n_plans, self.n_requests
        M, L = self.n_tokens, self.n_layers

        if active is None:
            active = np.ones(R, dtype=bool)
        active = np.asarray(active, dtype=bool)

        adm_on = self.admission_on and not zero_load
        shed = np.zeros((P, R), dtype=bool)
        retries = np.zeros((P, R), dtype=np.int64)
        ingress_extra = self.ingress_extra
        start_pref = self.start_pref
        if adm_on:
            ctrl = jnp.asarray(control_bin_flags(self.n_bins, qcfg.dt_s,
                                                 acfg.interval_s))
            admit_floor = np.ones((P, self.n_gw_stations, self.n_bins))
            margin = acfg.target_margin
            ttft0 = jnp.asarray(self._adm_ttft0)
            tpot0 = jnp.asarray(self._adm_tpot0)
            gw_idx = jnp.asarray(self._adm_gw_idx)
            exp_idx = jnp.asarray(self._adm_exp_idx)

        gw_wait = np.zeros((P, M, L))
        ex_max = np.zeros((P, M, L))
        gw_over = np.zeros((P, M, L), dtype=bool)
        ex_over = np.zeros((P, M, L), dtype=bool)
        n_iter = 1 if zero_load else max(1, qcfg.iterations)
        for _ in range(n_iter):
            layer_arr, exp_arr, tok_total, seg_incl, c0 = \
                self._schedule(gw_wait, ex_max, start_pref)
            work = self._bin_work(layer_arr, exp_arr,
                                  active[None, :] & ~shed)
            if zero_load:
                break
            batch_kw = None
            scan_work = work
            if self.batching is not None:
                wdec, cnt = self._bin_work_planes(
                    layer_arr, exp_arr, active[None, :] & ~shed)
                if adm_on:
                    # The law applies inside the admission jit (the
                    # window sum is pre-applied host-side so the call
                    # carries no static argument).
                    batch_kw = dict(
                        work_dec=jnp.asarray(wdec),
                        cnt_win=jnp.asarray(windowed_counts(
                            cnt, self._batch_window)),
                        table=jnp.asarray(self._batch_table),
                        bcap=jnp.asarray(np.float64(self._batch_cap)))
                else:
                    scan_work, _ = effective_work_np(
                        work, wdec, cnt, self._batch_table,
                        self._batch_cap, self._batch_window)
            if adm_on:
                pid_kw = None
                if acfg.policy == "pid":
                    gain = np.ones(P) if acfg.gain_scale is None \
                        else np.asarray(acfg.gain_scale, dtype=np.float64)
                    pid_kw = dict(kp=jnp.asarray(acfg.kp),
                                  ki=jnp.asarray(acfg.ki),
                                  kd=jnp.asarray(acfg.kd),
                                  gain=jnp.asarray(gain))
                wait, dropped, admit = admission_queue_scan(
                    jnp.asarray(work), jnp.asarray(qcfg.buffer_s),
                    qcfg.dt_s, ttft0, tpot0, ctrl, gw_idx, exp_idx,
                    jnp.ones((P, self.n_gw_stations)),
                    margin * acfg.ttft_target_s,
                    margin * acfg.tpot_target_s,
                    acfg.increase, acfg.decrease, acfg.admit_min,
                    batching=batch_kw, pid=pid_kw)
                # Monotone outer iteration: accumulate the trace as a
                # running minimum so the shed set only grows and the
                # fixed point converges from the congested side.
                admit_floor = np.minimum(admit_floor, np.asarray(admit))
                choice, shed = resolve_admission(
                    admit_floor, self._att_bin, self._att_station,
                    self._att_feasible, self._adm_u)
                retries = np.where(shed, 0, choice)
                ingress_extra = np.take_along_axis(
                    np.moveaxis(self._att_extra, 0, 1),     # (P, A, R)
                    retries[:, None, :], axis=1)[:, 0, :]   # (P, R)
                start_pref = req.arrival_s[None, :] + ingress_extra
            else:
                wait, dropped = _fleet_queue_scan(
                    jnp.asarray(scan_work), jnp.asarray(qcfg.buffer_s),
                    qcfg.dt_s)
            wait = np.asarray(wait)
            overload = np.asarray(dropped) > 0.0
            # Exposed for the re-placement controller: the live
            # (plan, satellite, bin) backlog of the last fleet scan.
            self.last_wait = wait
            gw_wait, ex_max, gw_over, ex_over = self._gather(
                wait, overload, layer_arr, exp_arr)
        # Fold the final gather into the schedule once more so reported
        # latencies reflect the waits actually found on the last pass.
        layer_arr, exp_arr, tok_total, seg_incl, c0 = \
            self._schedule(gw_wait, ex_max, start_pref)

        last_tok = self.first_tok + req.decode_len - 1
        ttft = ingress_extra + tok_total[:, :R]                   # (P, R)
        out = dict(
            ttft=ttft, e2e=ttft + seg_incl[:, last_tok],
            tok_total=tok_total,
            tok_over=gw_over.any(axis=2) | ex_over.any(axis=2),
            shed=shed, retries=retries, work_sum=work.sum(axis=2))
        return self._finalize(active, out, adm_on, kv_slots)

    def _finalize(self, active: np.ndarray, out: dict, adm_on: bool,
                  kv_slots: int | None = None, *,
                  names: list | None = None,
                  nan_tok: np.ndarray | None = None,
                  fail_ingress: np.ndarray | None = None,
                  migration_bytes: np.ndarray | None = None
                  ) -> TrafficResult:
        """Host post-processing shared by every execution path.

        Turns one run's raw outcome tensors (``ttft``/``e2e`` (P, R),
        ``tok_total`` (P, M), ``tok_over`` (P, M), ``shed``/``retries``
        (P, R), ``work_sum`` (P, S)) into per-plan
        :class:`~repro.traffic.metrics.PlanTraffic` rows: delivery
        failure aggregation, the static KV admission cap, spans,
        utilization and the latency quantiles' NaN masking.

        The plan axis P is taken from the outcome tensors (the joint
        control plane stitches a decided schedule row onto the
        candidate rows); the keyword overrides supply that extra row's
        per-plan tables, defaulting to this simulator's own.
        """
        qcfg, req = self.qcfg, self.requests
        R = self.n_requests
        P = out["ttft"].shape[0]
        names = self.batch.names if names is None else names
        nan_tok = self.nan_tok if nan_tok is None else nan_tok
        fail_ingress = (self.fail_ingress if fail_ingress is None
                        else fail_ingress)
        migration_bytes = (self.migration_bytes if migration_bytes is None
                           else migration_bytes)
        kv = qcfg.kv_slots if kv_slots is None else kv_slots
        ttft, e2e = out["ttft"], out["e2e"]
        tok_total, shed, retries = out["tok_total"], out["shed"], \
            out["retries"]

        fail_tok = nan_tok | out["tok_over"]
        failed = fail_tok[:, :R] \
            | _segment_any(fail_tok[:, R:], self.tok_req, R)      # (P, R)
        if adm_on:
            # Shed requests are accounted separately (not involuntary
            # drops); admitted requests entered via a feasible attempt.
            failed = failed | shed
        else:
            failed = failed | fail_ingress

        # KV admission cap: reject arrivals that would exceed the
        # in-flight budget (first-order: in-flight counted over all
        # offered requests).  The adaptive controller replaces this cap.
        admitted = np.ones((P, R), dtype=bool)
        if kv > 0 and not adm_on:
            comp = req.arrival_s[None, :] + np.nan_to_num(
                e2e, nan=np.inf, posinf=np.inf)
            comp = np.where(active[None, :], comp, -np.inf)
            n_inactive = int((~active).sum())
            arrived = np.cumsum(active)                           # (R,)
            # Batched searchsorted: one stable argsort per plan ranks
            # the sorted completion row against the (already sorted)
            # arrivals; completions sort before equal arrivals (stable,
            # first half), reproducing searchsorted side="right".
            keys = np.concatenate([
                np.sort(comp, axis=1),
                np.broadcast_to(req.arrival_s[None, :], (P, R))], axis=1)
            order = np.argsort(keys, axis=1, kind="stable")
            pos = np.empty_like(order)
            np.put_along_axis(pos, order, np.arange(2 * R)[None, :],
                              axis=1)
            done = pos[:, R:] - np.arange(R)[None, :] - n_inactive
            admitted = (arrived[None, :] - done) <= kv
        failed = failed | ~admitted

        served = active[None, :] & ~failed                        # (P, R)
        span = max(float(req.arrival_s[active].max()
                         - req.arrival_s[active].min()), qcfg.dt_s) \
            if active.any() else qcfg.dt_s
        # Offered utilization over the arrival window (> 1 = overload).
        util = out["work_sum"] / span                             # (P, S)

        plans_out = []
        for p in range(P):
            with np.errstate(invalid="ignore"):
                tpot = (e2e[p] - ttft[p]) / req.decode_len
            plans_out.append(PlanTraffic(
                plan_name=names[p],
                active=active.copy(),
                served=served[p],
                ttft_s=np.where(served[p], ttft[p], np.nan),
                tpot_s=np.where(served[p], tpot, np.nan),
                e2e_s=np.where(served[p], e2e[p], np.nan),
                decode_len=req.decode_len,
                station_util=util[p],
                span_s=span,
                token_total_s=tok_total[p],
                shed=(shed[p] & active) if adm_on else None,
                retries=np.where(served[p], retries[p], 0)
                if adm_on else None,
                migration_bytes=float(migration_bytes[p]),
            ))
        return TrafficResult(plans=plans_out, requests=req,
                             slots=self.slots, n_bins=self.n_bins,
                             dt_s=qcfg.dt_s)


def simulate_traffic(
    plans: list,
    topo: TopologySample,
    activation: ActivationModel,
    workload: MoEWorkload,
    compute: ComputeConfig,
    requests: RequestBatch,
    rng: np.random.Generator,
    qcfg: QueueConfig = QueueConfig(),
    ground: GroundSegment | None = None,
    **kwargs,
) -> TrafficResult:
    """One-shot convenience wrapper: build a :class:`FleetSim` and run it
    with every request active.

    Args:
        plans: Placement-plan sweep.
        topo: Sampled topology.
        activation: Expert-activation model.
        workload: FLOP model of the served MoE.
        compute: FLOPs -> seconds conversion.
        requests: The request trace.
        rng: Randomness for engine draws / admission uniforms.
        qcfg: Queueing/admission parameters.
        ground: Optional ground segment.
        **kwargs: Forwarded to :class:`FleetSim`.

    Returns:
        The :class:`~repro.traffic.metrics.TrafficResult` of one full run.
    """
    sim = FleetSim(plans, topo, activation, workload, compute, requests,
                   rng, qcfg=qcfg, ground=ground, **kwargs)
    return sim.run()

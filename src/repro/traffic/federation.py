"""Planet-scale federation: K constellations, one device launch.

A :class:`FederationSim` wraps K independently-planned constellations —
each an ordinary :class:`~repro.traffic.queueing.FleetSim` world with
its own topology, plans/schedules and admission config — behind one
shared :class:`~repro.traffic.ground.GroundSegment`, and serves the
whole federation through the *existing* fused fleet fixed point:

* **One launch.**  Member device tables are padded to common shapes
  (plans edge-repeated to ``P_max``, queue rows zero-extended to
  ``rows_max``; the time-bin count ``T`` must already agree — see
  :func:`build_federation`) and stacked along the F-leading sweep axis
  of :func:`repro.traffic.queueing._fused_core`.  A federation of K
  members under an S-point nested rate sweep runs as ``F = S * K``
  lanes of **one compile trace and one device launch** (pinned via
  ``FUSED_TRACE_COUNT``, the PR 5/9 pattern).  With overflow routing
  off, each lane's arithmetic is element-for-element the member's own
  plan-leading launch, so per-constellation results are **bitwise
  identical** to running each ``FleetSim`` alone — the parity anchor.

* **Overflow scheduling.**  Requests shed by one member's admission
  controller retry at the next-best constellation: the per-request
  preference order generalizes the per-constellation ranked-visibility
  gateway table across members
  (:func:`repro.traffic.ground.rank_constellations` over each member's
  ingress cost), and each forward is billed into TTFT/E2E like PR 3's
  gateway retries (terrestrial forward delay + the rejecting
  controller's retry backoff).  The host-side fixed point is monotone
  the same way ``admission_queue_scan``'s running-minimum admit trace
  is: a rejection is permanent (the request is never re-offered to
  that member), so per-member rejection sets only grow, hop pointers
  only advance, and the loop converges in at most ``K`` rounds of
  relaunches that all reuse the one compile-cache entry.

Padding is exact, not approximate: padded plan lanes repeat the last
real plan (they compute independently and are sliced off the outputs),
padded rows receive zero work and are never gathered, and shed requests
deposit nothing — so removing a rejected request from a member's mask
leaves that member's remaining outcomes bit-for-bit unchanged while the
receiving member only *gains* load (its shed set can only grow).
"""
from __future__ import annotations

import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from .batching import effective_work_np
from .ground import GroundSegment, rank_constellations
from .metrics import PlanTraffic, TrafficResult
from .queueing import _CHUNK_BLOCK, FleetSim, _fused_exec


@dataclasses.dataclass(frozen=True)
class FederationConfig:
    """Federation-scheduler knobs.

    Attributes:
        overflow: Route admission-shed requests to the next-best
            member constellation (requires every member to run the
            adaptive admission controller).  ``False`` serves each
            request only at its home constellation — the bitwise
            parity anchor against standalone ``FleetSim`` runs.
        forward_delay_s: Terrestrial latency billed per
            inter-constellation forward (on top of the rejecting
            controller's ``retry_backoff_s``).  ``None`` derives the
            mean off-diagonal ground delay of the shared ground
            segment when one is given, else 0.15 s.
        max_hops: Forward budget per request (default ``K - 1`` — at
            most one visit per member).
        max_rounds: Relaunch budget for the overflow fixed point
            (default ``K``; the monotone rejection sets converge in at
            most that many rounds when ``max_hops`` is ``K - 1``).
        serve_plan: Plan row of each member whose shed mask drives the
            routing decisions (results are still reported for every
            plan).
    """

    overflow: bool = True
    forward_delay_s: float | None = None
    max_hops: int | None = None
    max_rounds: int | None = None
    serve_plan: int = 0


@dataclasses.dataclass
class FederationResult:
    """Outcome of one federation run (one nested-sweep entry).

    Attributes:
        members: One :class:`~repro.traffic.metrics.TrafficResult` per
            member constellation, computed on its final offered mask
            with forwarding latency billed into TTFT/E2E.
        federated: Pooled :class:`~repro.traffic.metrics.PlanTraffic`
            over the members' ``serve_plan`` rows — the federation's
            own goodput/latency row.  Its ``retries`` column records
            inter-constellation hops; its ``shed`` column marks
            requests rejected by every member they could reach.
        assigned: (R,) final member index per request (-1 when the
            request ended up offered nowhere).
        hops: (R,) inter-constellation forwards each request took.
        n_rounds: Overflow fixed-point rounds executed (1 = no
            request moved).
        offered: (K, R) final per-member offered masks.
    """

    members: list
    federated: PlanTraffic
    assigned: np.ndarray
    hops: np.ndarray
    n_rounds: int
    offered: np.ndarray


def _edge_pad(a: np.ndarray, n: int, axis: int) -> np.ndarray:
    """Pad ``a`` to length ``n`` along ``axis`` by repeating its last
    entry (the exact-padding policy for the plan axis: a padded plan
    lane recomputes the last real plan and is sliced off on output)."""
    cur = a.shape[axis]
    if cur == n:
        return a
    idx = np.concatenate([np.arange(cur),
                          np.full(n - cur, cur - 1, dtype=np.int64)])
    return np.take(a, idx, axis=axis)


def _zero_pad(a: np.ndarray, n: int, axis: int) -> np.ndarray:
    """Pad ``a`` to length ``n`` along ``axis`` with zeros (the queue
    -row policy: padded rows receive no deposits and are never
    gathered)."""
    cur = a.shape[axis]
    if cur == n:
        return a
    shape = list(a.shape)
    shape[axis] = n - cur
    return np.concatenate([a, np.zeros(shape, dtype=a.dtype)], axis=axis)


class FederationSim:
    """K constellations behind one ground segment, one fused launch.

    Args:
        sims: Member :class:`~repro.traffic.queueing.FleetSim` worlds.
            They must share the request trace, the time-bin grid
            (``n_bins`` — build via :func:`build_federation` to
            equalize it), the queueing constants and — when admission
            is on — the controller law constants; topology, plans,
            schedules, ground visibility and admission *targets* are
            free per member.
        cfg: :class:`FederationConfig` (default: overflow on).
        home: Optional (R,) member index per request overriding the
            cost-based home assignment (benches use this to
            concentrate a hotspot on one member; -1 = use the cost
            ranking).
        ground: Optional shared ground segment — only used to derive
            ``forward_delay_s`` when the config leaves it ``None``.
    """

    def __init__(self, sims: list, cfg: FederationConfig | None = None,
                 *, home: np.ndarray | None = None,
                 ground: GroundSegment | None = None):
        if not sims:
            raise ValueError("a federation needs at least one member")
        self.sims = list(sims)
        self.cfg = cfg or FederationConfig()
        self._validate()
        K = len(self.sims)
        s0 = self.sims[0]
        self.n_members, self.n_requests = K, s0.n_requests
        self.n_bins = s0.n_bins
        self.requests = s0.requests
        self.admission_on = s0.admission_on
        self.serve_plan = self.cfg.serve_plan
        if not 0 <= self.serve_plan < min(s.n_plans for s in self.sims):
            raise ValueError("serve_plan out of range for some member")
        self._p_max = max(s.n_plans for s in self.sims)
        self._sr_max = max(s.n_rows for s in self.sims)
        # Member chunk gather indices remapped for the padded plan
        # block: the flat [layer | expert] pair per lane is laid out at
        # P_max plans, so expert-block sources shift up by the pad.
        self._fed_src = []
        for s in self.sims:
            gw_span = s.n_plans * s.n_tokens * s.n_layers
            shift = (self._p_max - s.n_plans) * s.n_tokens * s.n_layers
            self._fed_src.append(np.where(s._f_src < gw_span, s._f_src,
                                          s._f_src + shift))
        # Cross-constellation preference ranking: each member's ingress
        # cost for each request at the serve plan (+inf = its ground
        # segment cannot ingest the request), ranked best-first with
        # index tie-breaks — ground.ingress_ranked generalized across
        # members.
        costs = np.stack([
            np.where(s.fail_ingress[self.serve_plan], np.inf,
                     s.ingress_extra[self.serve_plan])
            for s in self.sims])                              # (K, R)
        self.ingress_cost = costs
        self.ranking = rank_constellations(costs)             # (R, K)
        self.feasible = np.isfinite(costs)                    # (K, R)
        best = self.ranking[:, 0]
        home_cost = np.where(self.feasible.any(axis=0), best, -1)
        if home is not None:
            home = np.asarray(home, dtype=np.int64)
            if home.shape != (self.n_requests,):
                raise ValueError(f"home must be ({self.n_requests},)")
            if (home >= K).any():
                raise ValueError("home index out of range")
            # Explicit homes must be feasible there; fall back to the
            # cost ranking (or -1) where they are not.
            ok = (home >= 0) & self.feasible[np.clip(home, 0, K - 1),
                                            np.arange(self.n_requests)]
            home_cost = np.where(ok, home, home_cost)
        self.home = home_cost                                 # (R,)
        if self.cfg.forward_delay_s is not None:
            self.forward_delay_s = float(self.cfg.forward_delay_s)
        elif ground is not None and ground.n_stations > 1:
            gd = ground.ground_delay_s
            off = ~np.eye(ground.n_stations, dtype=bool)
            self.forward_delay_s = float(gd[off].mean())
        else:
            self.forward_delay_s = 0.15
        self.max_hops = (K - 1 if self.cfg.max_hops is None
                         else int(self.cfg.max_hops))
        self.max_rounds = (K if self.cfg.max_rounds is None
                           else int(self.cfg.max_rounds))
        self._dev_cache: dict = {}

    # ------------------------------------------------------------- #
    # Validation + padded device tables
    # ------------------------------------------------------------- #

    def _validate(self) -> None:
        s0 = self.sims[0]
        req0 = s0.requests
        for i, s in enumerate(self.sims[1:], start=1):
            r = s.requests
            if not (np.array_equal(req0.arrival_s, r.arrival_s)
                    and np.array_equal(req0.prompt_len, r.prompt_len)
                    and np.array_equal(req0.decode_len, r.decode_len)
                    and np.array_equal(req0.station, r.station)):
                raise ValueError(
                    f"member {i} serves a different request trace — a "
                    f"federation shares one global trace")
            if s.n_bins != s0.n_bins:
                raise ValueError(
                    f"member {i} has {s.n_bins} time bins vs "
                    f"{s0.n_bins}: the fused kernel's bin clipping is "
                    f"static in T, so members must share n_bins — "
                    f"rebuild the shorter ones with min_bins="
                    f"{max(s.n_bins, s0.n_bins)} (build_federation "
                    f"does this)")
            q0, q = s0.qcfg, s.qcfg
            if (q0.dt_s, q0.buffer_s, q0.iterations) != \
                    (q.dt_s, q.buffer_s, q.iterations):
                raise ValueError(
                    f"member {i} queueing constants differ "
                    f"(dt_s/buffer_s/iterations are shared kernel "
                    f"consts)")
            if s.admission_on != s0.admission_on:
                raise ValueError(
                    "members must all run admission, or none")
            if s.admission_on:
                a0, a = q0.admission, q.admission
                same = (a0.policy == a.policy
                        and a0.increase == a.increase
                        and a0.decrease == a.decrease
                        and a0.admit_min == a.admit_min
                        and a0.interval_s == a.interval_s
                        and a0.max_retries == a.max_retries)
                if a0.policy == "pid":
                    same = same and (a0.kp, a0.ki, a0.kd) == \
                        (a.kp, a.ki, a.kd) \
                        and a0.gain_scale is None \
                        and a.gain_scale is None
                if not same:
                    raise ValueError(
                        f"member {i} admission law differs (the AIMD/"
                        f"PID constants are shared kernel consts; "
                        f"targets may differ, the law may not)")
            if not np.array_equal(s0.gw_service, s.gw_service):
                raise ValueError(
                    f"member {i} gateway service times differ — "
                    f"federation lanes share the per-token service "
                    f"array (use one workload/service model)")
            if (s.n_tokens, s.n_layers) != (s0.n_tokens, s0.n_layers):
                raise ValueError(
                    f"member {i} token/layer grid differs")
            if s._ex_rowc.shape[-1] != s0._ex_rowc.shape[-1]:
                raise ValueError(
                    f"member {i} expert gather depth differs")
            if s.admission_on and \
                    s._adm_exp_rowc.shape[-1] != s0._adm_exp_rowc.shape[-1]:
                raise ValueError(
                    f"member {i} admission station-map width differs")
            if s.admission_on and \
                    s._adm_ttft0.shape[1] != s0._adm_ttft0.shape[1]:
                raise ValueError(
                    f"member {i} gateway count differs — members share "
                    f"one ground segment (G is a kernel const)")
            if s.probes is not None or s0.probes is not None:
                raise ValueError(
                    "probes are not supported on federation launches")
            b0, b = s0.batching, s.batching
            if (b0 is None) != (b is None):
                raise ValueError(
                    "members must all batch, or none")
            if b0 is not None and not (
                    np.array_equal(s0._batch_table, s._batch_table)
                    and s0._batch_cap == s._batch_cap
                    and s0._batch_window == s._batch_window):
                raise ValueError(
                    f"member {i} batching table differs (shared const)")
        if self.cfg.overflow and not s0.admission_on:
            raise ValueError(
                "overflow routing re-routes admission-shed requests — "
                "it needs every member to run the adaptive admission "
                "controller (or pass FederationConfig(overflow=False))")

    def _stacked_consts(self) -> dict:
        """K-leading numpy stack of the members' device tables, padded
        to (P_max, rows_max)."""
        P, SR = self._p_max, self._sr_max
        sims = self.sims

        def plans(attr, axis=0):
            return np.stack([_edge_pad(getattr(s, attr), P, axis)
                             for s in sims])

        base = dict(
            eff_layer=plans("eff_layer"),            # (K, P, M, L)
            tok_base=plans("tok_base"),              # (K, P, M)
            ingress_extra0=plans("ingress_extra"),   # (K, P, R)
            gw_rows=plans("_gw_rowc"),               # (K, P, M, L)
            ex_rows=plans("_ex_rowc"),               # (K, P, M, L, I)
            gw_b0=plans("_gw_b0"), gw_fin0=plans("_gw_fin0"),
            ex_b0=plans("_ex_b0"), ex_fin0=plans("_ex_fin0"),
        )
        if any(s._mig_rm is not None for s in sims):
            base["mig_dense_f"] = np.stack([
                _zero_pad(s._mig_rm, SR, 0) if s._mig_rm is not None
                else np.zeros((SR, self.n_bins))
                for s in sims])                      # (K, rows, T)
        if self.admission_on:
            f32 = np.float32
            base.update(
                ttft0=np.stack([_edge_pad(s._adm_ttft0.astype(f32), P, 0)
                                for s in sims]),     # (K, P, G)
                tpot0=np.stack([_edge_pad(s._adm_tpot0.astype(f32), P, 0)
                                for s in sims]),     # (K, P)
                # Per-bin station maps stay T-leading with the lane
                # axis second: (T, K, P, L) / (T, K, P, LI).
                gw_rows_bin=np.stack(
                    [_edge_pad(s._adm_gw_rowc, P, 1) for s in sims],
                    axis=1),
                exp_rows_bin=np.stack(
                    [_edge_pad(s._adm_exp_rowc, P, 1) for s in sims],
                    axis=1),
                # Per-member attempt tables (the new (F, A, R) kernel
                # branch): retry gateways/bins follow each member's own
                # ground visibility.
                att_bin=np.stack([s._att_bin for s in sims]),
                att_station=np.stack([s._att_station for s in sims]),
                att_feasible=np.stack([
                    _edge_pad(np.moveaxis(s._att_feasible, 1, 0), P, 0)
                    for s in sims]),                 # (K, P, A, R)
                att_extra=np.stack([
                    _edge_pad(np.moveaxis(s._att_extra, 0, 1), P, 0)
                    for s in sims]),                 # (K, P, A, R)
                adm_u=np.stack([s._adm_u for s in sims]),  # (K, A, R)
            )
        return base

    def _device_consts(self, n_sweep: int) -> dict:
        """The fused kernel's consts pytree for ``F = n_sweep * K``
        lanes (lane ``f = s * K + k`` carries member ``k``): the
        K-leading stack tiled along the sweep, plus the shared
        request/clock tables taken from member 0."""
        if n_sweep in self._dev_cache:
            return self._dev_cache[n_sweep]
        s0 = self.sims[0]
        qcfg = s0.qcfg
        base = self._stacked_consts()
        with jax.enable_x64():
            d = {}
            for key, a in base.items():
                if key in ("gw_rows_bin", "exp_rows_bin"):
                    reps = (1, n_sweep) + (1,) * (a.ndim - 2)
                else:
                    reps = (n_sweep,) + (1,) * (a.ndim - 1)
                d[key] = jnp.asarray(np.tile(a, reps))
            d.update(
                dt=jnp.asarray(float(qcfg.dt_s)),
                cap32=jnp.asarray(float(qcfg.buffer_s),
                                  dtype=jnp.float32),
                dt32=jnp.asarray(float(qcfg.dt_s), dtype=jnp.float32),
                gw_service=jnp.asarray(s0.gw_service),
                arrival_s=jnp.asarray(self.requests.arrival_s),
                first_tok=jnp.asarray(s0.first_tok),
                tok_req=jnp.asarray(s0.tok_req),
                last_tok=jnp.asarray(
                    s0.first_tok + self.requests.decode_len - 1),
            )
            if self.admission_on:
                sd = s0._device_tables()
                for key in ("ctrl", "increase", "decrease", "admit_min"):
                    d[key] = sd[key]
                if qcfg.admission.policy == "pid":
                    d["pid_kp"] = sd["pid_kp"]
                    d["pid_ki"] = sd["pid_ki"]
                    d["pid_kd"] = sd["pid_kd"]
                    d["pid_gain"] = jnp.asarray(
                        np.ones(self._p_max, dtype=np.float32))
        self._dev_cache[n_sweep] = d
        return d

    # ------------------------------------------------------------- #
    # Launch
    # ------------------------------------------------------------- #

    def _launch(self, offered: np.ndarray) -> dict:
        """One fused launch over ``F = n_sweep * K`` federation lanes.

        Mirrors :meth:`FleetSim._launch` exactly, per lane: the chunk
        compaction streams one lane at a time (bounded shards — the
        dense (F, n_chunks) activity matrix never materializes), lane
        ``f = s * K + k`` deposits member ``k``'s active chunks under
        sweep entry ``s``'s mask, and the iteration-1 plane is one
        host bincount per lane.

        Args:
            offered: (n_sweep, K, R) bool per-member offered masks.

        Returns:
            The fused output dict as host arrays, leading axis F.
        """
        return self._execute(self._prepare(offered))

    def _prepare(self, offered: np.ndarray) -> dict:
        """Host side of a launch: per-lane chunk compaction and the
        iteration-1 deposit planes.  Split from :meth:`_execute` so the
        benchmark can bill host prep and device time separately."""
        n_sweep, K, R = offered.shape
        F = n_sweep * K
        P, SR, T = self._p_max, self._sr_max, self.n_bins
        s0 = self.sims[0]
        M, L = s0.n_tokens, s0.n_layers
        pml2 = 2 * P * M * L
        batching = s0.batching is not None

        lane_cols: list[tuple[int, "FleetSim", np.ndarray]] = []
        for s in range(n_sweep):
            for k, sim in enumerate(self.sims):
                cid = np.flatnonzero(offered[s, k][sim._f_req])
                lane_cols.append((s * K + k, sim, cid))
        n = sum(c.size for _, _, c in lane_cols)
        n_pad = max(-(-n // _CHUNK_BLOCK), 1) * _CHUNK_BLOCK

        src = np.zeros(n_pad, dtype=np.int64)
        offs = np.zeros(n_pad, dtype=np.int64)
        work = np.zeros(n_pad)
        fprow = np.zeros(n_pad, dtype=np.int32)
        fpr = np.zeros(n_pad, dtype=np.int64)
        wdec = np.zeros(n_pad) if batching else None
        cntw = np.zeros(n_pad) if batching else None
        plane0 = np.zeros((F, SR, T))
        plane0_dec = np.zeros((F, SR, T)) if batching else None
        cnt0 = np.zeros((F, SR, T)) if batching else None

        pos = 0
        for f, sim, cid in lane_cols:
            m = cid.size
            k = f % K
            sl = slice(pos, pos + m)
            src[sl] = f * pml2 + self._fed_src[k][cid]
            offs[sl] = sim._f_offs[cid]
            work[sl] = sim._f_work[cid]
            fprow[sl] = np.int32(f * SR) + sim._f_rowc[cid]
            fpr[sl] = f * (P * R) + sim._f_pr[cid]
            if batching:
                wdec[sl] = sim._f_wdec[cid]
                cntw[sl] = sim._f_cntw[cid]
            pos += m
            flat0 = sim._f_rowc[cid].astype(np.int64) * T \
                + sim._f_bins0[cid]
            w0 = sim._f_work[cid] * sim._f_fin0[cid]
            plane0[f] = np.bincount(
                flat0, weights=w0, minlength=SR * T
            ).reshape(SR, T).astype(np.float64)
            if sim._mig_rm is not None:
                plane0[f, :sim.n_rows] += sim._mig_rm
            if batching:
                plane0_dec[f] = np.bincount(
                    flat0, weights=sim._f_wdec[cid] * sim._f_fin0[cid],
                    minlength=SR * T).reshape(SR, T)
                cnt0[f] = np.bincount(
                    flat0, weights=sim._f_cntw[cid] * sim._f_fin0[cid],
                    minlength=SR * T).reshape(SR, T)

        work0_sum = plane0.sum(axis=2)
        batch_np: dict = {}
        batch_window = 0
        if batching:
            plane0, _ = effective_work_np(
                plane0, plane0_dec, cnt0, s0._batch_table,
                s0._batch_cap, s0._batch_window)
            batch_np = dict(table=s0._batch_table,
                            bcap=np.float64(s0._batch_cap))
            batch_window = s0._batch_window

        chunks = dict(src=src, offs=offs, work=work, fprow=fprow)
        if self.admission_on:
            chunks["fpr"] = fpr
            tt = np.empty(F)
            tp = np.empty(F)
            for k, sim in enumerate(self.sims):
                acfg = sim.qcfg.admission
                m = acfg.target_margin
                tt[k::K] = m * acfg.ttft_target_s
                tp[k::K] = m * acfg.tpot_target_s
        else:
            tt = np.zeros(F)
            tp = np.zeros(F)
        if batching:
            chunks["wdec"], chunks["cntw"] = wdec, cntw

        return dict(chunks=chunks, plane0=plane0, work0_sum=work0_sum,
                    tt=tt, tp=tp, batch_np=batch_np,
                    batch_window=batch_window, n_sweep=n_sweep,
                    T=T, SR=SR)

    def _execute(self, prep: dict) -> dict:
        """Device side of a launch: move the prepared chunk stream to
        the device and run the fused kernel once."""
        s0 = self.sims[0]
        with jax.enable_x64(), warnings.catch_warnings():
            warnings.filterwarnings("ignore", message=".*[Dd]onat")
            out = _fused_exec(
                self._device_consts(prep["n_sweep"]),
                {k: jnp.asarray(v) for k, v in prep["chunks"].items()},
                jnp.asarray(prep["plane0"].astype(np.float32)),
                jnp.asarray(prep["work0_sum"]),
                jnp.asarray(prep["tt"]), jnp.asarray(prep["tp"]), {},
                {k: jnp.asarray(v) for k, v in prep["batch_np"].items()},
                max(1, s0.qcfg.iterations), prep["T"], prep["SR"],
                self.admission_on, s0._deposit_mode(), False,
                None, prep["batch_window"])
            out = {k: jax.tree_util.tree_map(np.asarray, v)
                   for k, v in out.items()}
        return out

    # ------------------------------------------------------------- #
    # Overflow fixed point + result assembly
    # ------------------------------------------------------------- #

    def run_many(self, masks: np.ndarray | None = None, *,
                 overflow: bool | None = None) -> list[FederationResult]:
        """Serve a nested sweep of global activity masks — the whole
        federation, every sweep entry, in one compile trace.

        The first launch covers every (sweep entry, member) lane; each
        overflow round removes newly-rejected requests from the
        rejecting member (permanently — the monotone invariant) and
        offers them to the next-best feasible member on their ranking,
        then relaunches the *same shapes* (compile-cache hit, no new
        trace).  The loop stops when no request moves or after
        ``max_rounds`` launches.

        Args:
            masks: (n_sweep, R) bool global activity masks (None = one
                all-active entry).
            overflow: Override the config's overflow switch for this
                run.

        Returns:
            One :class:`FederationResult` per sweep entry.
        """
        R, K = self.n_requests, self.n_members
        if masks is None:
            masks = np.ones((1, R), dtype=bool)
        masks = np.asarray(masks, dtype=bool)
        if masks.ndim != 2 or masks.shape[1] != R:
            raise ValueError(f"masks must be (n_sweep, {R})")
        n_sweep = masks.shape[0]
        route = self.cfg.overflow if overflow is None else bool(overflow)
        if route and not self.admission_on:
            raise ValueError("overflow routing needs admission")

        # Home assignment: each active request starts at its preferred
        # feasible member; requests no member can ingest start nowhere.
        offered = np.zeros((n_sweep, K, R), dtype=bool)
        for k in range(K):
            offered[:, k] = masks & (self.home == k)[None, :]
        visited = offered.copy()                     # never re-offer
        assigned = np.where(masks, self.home[None, :], -1)  # (n_sweep, R)
        hops = np.zeros((n_sweep, R), dtype=np.int64)
        extra_s = np.zeros((n_sweep, R))

        sp = self.serve_plan
        n_rounds = 0
        while True:
            out = self._launch(offered)
            n_rounds += 1
            if not route or n_rounds >= self.max_rounds:
                break
            moved = False
            for s in range(n_sweep):
                for k in range(K):
                    f = s * K + k
                    rej = out["shed"][f, sp] & offered[s, k]
                    if not rej.any():
                        continue
                    # Permanent rejection at k: shed requests deposit
                    # nothing, so dropping them leaves k's remaining
                    # outcomes bit-identical.
                    offered[s, k][rej] = False
                    backoff = self.sims[k].qcfg.admission.retry_backoff_s
                    for r in np.flatnonzero(rej):
                        assigned[s, r] = -1
                        if hops[s, r] >= self.max_hops:
                            continue
                        for k2 in self.ranking[r]:
                            if visited[s, k2, r] or \
                                    not self.feasible[k2, r]:
                                continue
                            offered[s, k2, r] = True
                            visited[s, k2, r] = True
                            assigned[s, r] = k2
                            hops[s, r] += 1
                            extra_s[s, r] += \
                                self.forward_delay_s + backoff
                            moved = True
                            break
            if not moved:
                break

        return [self._assemble(masks[s], offered[s], out, s,
                               assigned[s], hops[s], extra_s[s],
                               n_rounds)
                for s in range(n_sweep)]

    def run(self, active: np.ndarray | None = None, *,
            overflow: bool | None = None) -> FederationResult:
        """Single-entry convenience wrapper around :meth:`run_many`."""
        if active is None:
            active = np.ones(self.n_requests, dtype=bool)
        return self.run_many(np.asarray(active, dtype=bool)[None, :],
                             overflow=overflow)[0]

    def _assemble(self, active, offered, out, s, assigned, hops,
                  extra_s, n_rounds) -> FederationResult:
        """Slice one sweep entry's lanes out of the fused output, bill
        the forwarding latency, and pool the federation row."""
        K, sp = self.n_members, self.serve_plan
        members = []
        for k, sim in enumerate(self.sims):
            f = s * K + k
            o = dict(
                ttft=out["ttft"][f, :sim.n_plans],
                e2e=out["e2e"][f, :sim.n_plans],
                tok_total=out["tok_total"][f, :sim.n_plans],
                tok_over=out["tok_over"][f, :sim.n_plans],
                shed=out["shed"][f, :sim.n_plans],
                retries=out["retries"][f, :sim.n_plans],
                work_sum=sim._expand_rows(
                    out["work_sum"][f, :sim.n_rows]),
            )
            res = sim._finalize(offered[k], o, self.admission_on)
            if extra_s.any():
                res = dataclasses.replace(res, plans=[
                    p.with_added_latency(extra_s) for p in res.plans])
            members.append(res)

        # Pooled federation row over the serve-plan rows: the offered
        # masks are disjoint per round, so served sets never overlap.
        req = self.requests
        R = self.n_requests
        nan = np.full(R, np.nan)
        served = np.zeros(R, dtype=bool)
        ttft, tpot, e2e = nan.copy(), nan.copy(), nan.copy()
        retries = np.zeros(R, dtype=np.int64)
        shed_any = np.zeros(R, dtype=bool)
        mig = 0.0
        utils, toks = [], []
        for k, res in enumerate(members):
            row = res.plans[sp]
            sk = row.served
            served |= sk
            ttft[sk] = row.ttft_s[sk]
            tpot[sk] = row.tpot_s[sk]
            e2e[sk] = row.e2e_s[sk]
            retries[sk] = hops[sk]
            if row.shed is not None:
                # Final-round sheds only: earlier rejections already
                # left this member's offered mask.
                shed_any |= row.shed
            mig += row.migration_bytes
            utils.append(row.station_util)
            toks.append(row.token_total_s)
        span = max(float(req.arrival_s[active].max()
                         - req.arrival_s[active].min()),
                   self.sims[0].qcfg.dt_s) if active.any() \
            else self.sims[0].qcfg.dt_s
        federated = PlanTraffic(
            plan_name="federation",
            active=active.copy(),
            served=served,
            ttft_s=ttft, tpot_s=tpot, e2e_s=e2e,
            decode_len=req.decode_len,
            station_util=np.concatenate(utils),
            span_s=span,
            token_total_s=np.concatenate(toks),
            shed=(active & ((assigned < 0) | shed_any))
            if self.admission_on else None,
            retries=np.where(served, retries, 0)
            if self.admission_on else None,
            migration_bytes=mig,
        )
        return FederationResult(
            members=members, federated=federated, assigned=assigned,
            hops=hops, n_rounds=n_rounds, offered=offered.copy())


def build_federation(factories: list, cfg: FederationConfig | None = None,
                     **kwargs) -> FederationSim:
    """Construct member worlds on one shared time-bin grid.

    Each factory is a callable taking a ``min_bins`` keyword and
    returning a :class:`~repro.traffic.queueing.FleetSim` (e.g. a
    ``functools.partial`` over ``FleetSim`` or
    :func:`repro.traffic.scenarios.make_sim`).  Members are built
    once, then any member whose natural horizon came up short is
    rebuilt with ``min_bins`` pinned to the federation maximum — the
    fused kernel's bin clipping is static in T, so sharing the grid is
    what makes the padded stacking exact.

    Args:
        factories: K callables ``f(min_bins=...) -> FleetSim``.
        cfg: Passed through to :class:`FederationSim`.
        **kwargs: Passed through to :class:`FederationSim` (``home``,
            ``ground``).

    Returns:
        The federation over the (re)built members.
    """
    sims = [f(min_bins=0) for f in factories]
    t_max = max(s.n_bins for s in sims)
    sims = [s if s.n_bins == t_max else f(min_bins=t_max)
            for s, f in zip(sims, factories)]
    return FederationSim(sims, cfg, **kwargs)

"""Plain reference of one scheduling round, independent of the program.

It imports nothing of ``repro``. From a run it takes only the question each
round was asked (the jobs due, the decision time, the free servers) and the
telemetry arrays (input data, like a model's weights), and from the
configuration file the constants the policy states. It prices the round as
the paper's Eqs 1-8 and 11 say, in float64 numpy: the reactive instance
(regions plus the defer arc) or the temporal one (regions x forecast slots,
with its own Holt-Winters forecast), or the Eqs 12-13 soft instance where
no hard assignment exists. ``lp_optimum`` solves the transport LP exactly
with HiGHS (the constraint matrix is totally unimodular, so the LP optimum
is the integer optimum).

``check_round`` holds one round's answer against it: hard or soft as the
reference says, every job assigned, no column over its capacity, no job on
an arc the reference forbids. ``gaps`` then prices the served assignment
and the program's Sinkhorn plan before rounding on the reference's own
costs and compares both with the optimum.
"""
from __future__ import annotations

import dataclasses
import math
from typing import List, Optional, Sequence

import numpy as np
from scipy import optimize, sparse

HOUR = 3600.0


@dataclasses.dataclass
class Round:
    """One round as the reference needs it: the question and the answer."""
    now_s: float
    E: np.ndarray            # [M] energy kWh
    t: np.ndarray            # [M] exec seconds
    home: np.ndarray         # [M]
    size: np.ndarray         # [M] package bytes
    tol: np.ndarray          # [M] tolerance fraction
    submit: np.ndarray       # [M]
    capacity: np.ndarray     # [R] free servers offered
    assign: np.ndarray       # [M] column the program chose, -1 for none
    softened: bool
    # [M, C] the transport plan the program handed to its host rounding,
    # each row a distribution over the arcs it allowed; None where no solve
    # rounded one.
    plan: Optional[np.ndarray]

    @classmethod
    def of(cls, jobs: Sequence, now_s: float, capacity, assign, softened,
           plan) -> "Round":
        def col(name):
            return np.array([getattr(j, name) for j in jobs], np.float64)
        return cls(now_s=float(now_s), E=col("energy_kwh"),
                   t=col("exec_time_s"),
                   home=np.array([j.home_region for j in jobs], np.int64),
                   size=col("package_bytes"), tol=col("tolerance"),
                   submit=col("submit_time_s"),
                   capacity=np.asarray(capacity, np.int64).copy(),
                   assign=np.asarray(assign, np.int64).copy(),
                   softened=bool(softened), plan=plan)


@dataclasses.dataclass
class Instance:
    cost: Optional[np.ndarray]   # [M, C]; None where only the mask was built
    allowed: np.ndarray          # [M, C]
    capacity: np.ndarray         # [C]
    soft: bool
    # How far inside its bound each arc lies, in the bound's own units;
    # arcs within ``band`` of the bound may fall either way in float32.
    margin: np.ndarray           # [M, C]
    band: np.ndarray             # [M, C]


class Reference:
    """Prices rounds of one configuration (see module docstring)."""

    def __init__(self, tele: dict, config: dict, round_s: float):
        self.ci, self.ewif, self.wue = (np.asarray(tele[k], np.float64)
                                        for k in ("ci", "ewif", "wue"))
        self.pue = np.asarray(tele["pue"], np.float64)
        self.wsf = np.asarray(tele["wsf"], np.float64)
        self.bw = np.asarray(tele["bw_gbps"], np.float64)
        self.rtt = np.asarray(tele["rtt_s"], np.float64)
        self.T, self.R = self.ci.shape
        srv = config["server"]
        self.life = srv["lifetime_s"]
        self.emb_g = srv["embodied_gco2"]
        # Eq 4: manufacturing energy (embodied carbon / fab grid intensity)
        # times the fab grid's water intensity and scarcity.
        self.emb_w = (srv["embodied_gco2"] / srv["ci_mfg_g_per_kwh"]
                      * srv["ewif_mfg_l_per_kwh"] * (1.0 + srv["wsf_mfg"]))
        o = config["objective"]
        self.lam_c, self.lam_w, self.lam_ref = (o["lam_co2"], o["lam_h2o"],
                                                o["lam_ref"])
        self.window, self.raw_window = o["history_window"], o["raw_window"]
        self.sigma = o["sigma"]
        self.round_s = float(round_s)
        self.defer = config.get("defer_arc")
        self.fc = config.get("forecast")
        self._fits = {}

    # -- telemetry ----------------------------------------------------------

    def snap(self, t_s: float):
        """(ci, ewif, wue) linearly interpolated between hourly samples,
        wrapping at the end of the series."""
        h = int(t_s // HOUR) % self.T
        h2 = (h + 1) % self.T
        w = (t_s % HOUR) / HOUR
        return tuple((1 - w) * x[h] + w * x[h2]
                     for x in (self.ci, self.ewif, self.wue))

    def history(self, observed: Sequence[float]):
        """Eq 8's reference term from the last ``window`` observed
        snapshots, and the raw trailing means of the last ``raw_window``
        (None below two observations) that price the defer arc."""
        snaps = [self.snap(t) for t in observed]
        ci_n, wi_n = [], []
        for ci, ewif, wue in snaps[-self.window:]:
            wi = (wue + self.pue * ewif) * (1.0 + self.wsf)
            ci_n.append(ci / max(ci.max(), 1e-9))
            wi_n.append(wi / max(wi.max(), 1e-9))
        ref = self.lam_ref * (self.lam_c * np.mean(ci_n, axis=0)
                              + self.lam_w * np.mean(wi_n, axis=0))
        raw = None
        if len(snaps) >= 2:
            raw = np.mean(np.array(snaps[-self.raw_window:]), axis=0)
        return ref, raw

    # -- Eqs 1-5 ------------------------------------------------------------

    def carbon(self, E, t, ci):
        return E * ci + t / self.life * self.emb_g

    def water(self, E, t, ewif, wue):
        return (self.pue * E * ewif * (1.0 + self.wsf)
                + E * wue * (1.0 + self.wsf) + t / self.life * self.emb_w)

    def latency(self, r: Round) -> np.ndarray:
        bw = np.maximum(self.bw[r.home] * 1e9, 1.0)
        lat = 2.0 + self.rtt[r.home] + r.size[:, None] / bw
        lat[np.arange(len(r.home)), r.home] = 0.0
        return lat

    def _base(self, r: Round, ref_row):
        ci, ewif, wue = self.snap(r.now_s)
        E, t = r.E[:, None], r.t[:, None]
        co2 = self.carbon(E, t, ci[None, :])
        h2o = self.water(E, t, ewif[None, :], wue[None, :])
        cmax = np.maximum(co2.max(axis=1), 1e-9)
        wmax = np.maximum(h2o.max(axis=1), 1e-9)
        base = (self.lam_c * co2 / cmax[:, None]
                + self.lam_w * h2o / wmax[:, None] + ref_row[None, :])
        return base, cmax, wmax

    # -- instances ----------------------------------------------------------

    def eq11(self, r: Round, lat):
        """Eq 11 with the wait so far: the overrun as a fraction of the
        exec time must stay within the tolerance."""
        waited = np.maximum(r.now_s - r.submit, 0.0)
        overrun = (lat + waited[:, None]) / np.maximum(r.t[:, None], 1e-9)
        margin = r.tol[:, None] + 1e-12 - overrun
        return overrun, margin

    def slack(self, r: Round):
        return r.tol * r.t - np.maximum(r.now_s - r.submit, 0.0)

    def instance(self, r: Round, observed: Sequence[float],
                 with_cost: bool) -> Instance:
        """The round's instance. ``observed`` holds the decision times of
        every round priced so far, this one last; costs are built only
        ``with_cost``."""
        lat = self.latency(r)
        overrun, margin0 = self.eq11(r, lat)
        band0 = np.full_like(margin0, 1e-9)
        if self.fc is not None:
            margin, band, cap = self._temporal_mask(r, lat, margin0, band0)
        else:
            margin, band, cap = self._reactive_mask(r, margin0, band0,
                                                    len(observed) >= 2)
        allowed = margin >= 0
        soft = (not allowed.any(axis=1).all()) or cap.sum() < len(r.t)
        if soft:
            # Eqs 12-13: every arc allowed at the penalty sigma·excess.
            margin = np.full((len(r.t), self.R), np.inf)
            allowed = margin >= 0
            band = np.zeros_like(margin)
            cap = r.capacity.astype(np.float64)
        cost = None
        if with_cost:
            ref_row, raw = self.history(observed)
            base, cmax, wmax = self._base(r, ref_row)
            if soft:
                cost = base + self.sigma * np.maximum(
                    overrun - r.tol[:, None], 0.0)
            elif self.fc is not None:
                cost = self._temporal_cost(r, ref_row, observed)
            elif raw is not None:
                cost = np.concatenate(
                    [base, self._defer_cost(r, ref_row, raw, cmax, wmax)],
                    axis=1)
            else:
                cost = base
        return Instance(cost=cost, allowed=allowed, capacity=cap, soft=soft,
                        margin=margin, band=band)

    def _reactive_mask(self, r, margin0, band0, with_defer: bool):
        cap = r.capacity.astype(np.float64)
        if not with_defer:
            return margin0, band0, cap
        reserve = max(self.defer["slack_s"], 2.0 * self.round_s)
        wait = (self.slack(r) - reserve)[:, None]
        # The defer arc needs strictly more slack than the reserve.
        wait = np.where(wait > 0, wait, -1.0)
        return (np.concatenate([margin0, wait], axis=1),
                np.concatenate([band0, np.full_like(wait, 1e-6)], axis=1),
                np.concatenate([cap, [len(r.t)]]))

    def _defer_cost(self, r, ref_row, raw, cmax, wmax):
        """The defer arc: the trailing-mean cost of the cheapest region plus
        the margin, normalised like the real arcs."""
        ci, ewif, wue = raw
        E, t = r.E[:, None], r.t[:, None]
        h = (self.lam_c * self.carbon(E, t, ci[None, :]) / cmax[:, None]
             + self.lam_w * self.water(E, t, ewif[None, :], wue[None, :])
             / wmax[:, None] + ref_row[None, :])
        return (h.min(axis=1) + self.defer["margin"])[:, None]

    # -- the temporal instance ---------------------------------------------

    def _offsets(self):
        return np.arange(self.fc["horizon_slots"]) * self.fc["slot_s"]

    def _temporal_mask(self, r, lat, margin0, band0):
        off = self._offsets()
        guard = max(self.fc["guard_s"], 2.0 * self.round_s)
        budget = self.slack(r)
        need = off[None, :, None] + lat[:, None, :] + guard
        margin = budget[:, None, None] + 1e-9 - need
        # The program computes this mask in float32 on the device.
        band = 1e-5 * np.maximum(np.abs(budget)[:, None, None], need) + 1e-3
        margin[:, 0, :] = margin0
        band[:, 0, :] = band0
        S = len(off)
        M = len(r.t)
        return (margin.reshape(M, S * self.R), band.reshape(M, S * self.R),
                np.tile(r.capacity.astype(np.float64), S))

    def _temporal_cost(self, r, ref_row, observed):
        off = self._offsets()
        S, R, M = len(off), self.R, len(r.t)
        fc = self._forecast(r, off, observed)
        t0 = np.broadcast_to(r.now_s + off[None, :], (M, S)).ravel()
        t1 = (r.now_s + off[None, :] + r.t[:, None]).ravel()
        rows = fc.mean_many(t0, t1, "mean")
        if self.fc["risk"] > 0:
            shade = self.fc["risk"] * (fc.mean_many(t0, t1, "hi") - rows)
            shade[np.arange(t0.size) % S == 0] = 0.0
            rows = rows + shade
        rows = np.maximum(rows, 1e-6).reshape(M, S, 3 * R)
        ci, ewif, wue = rows[..., :R], rows[..., R:2 * R], rows[..., 2 * R:]
        E, t = r.E[:, None, None], r.t[:, None, None]
        co2 = self.carbon(E, t, ci)
        h2o = self.water(E, t, ewif, wue)
        cmax = np.maximum(co2.max(axis=(1, 2)), 1e-9)
        wmax = np.maximum(h2o.max(axis=(1, 2)), 1e-9)
        obj = (self.lam_c * co2 / cmax[:, None, None]
               + self.lam_w * h2o / wmax[:, None, None]
               + ref_row[None, None, :]
               + self.fc["defer_eps"] * np.arange(S)[None, :, None])
        return obj.reshape(M, S * R)

    def _forecast(self, r, off, observed) -> "Forecast":
        """The forecast in force at this round: fit at the round's hour (a
        refit each new hour) on the ``warmup_hours`` hours ending there,
        wrapped over the telemetry."""
        h = int(r.now_s // HOUR)
        if h not in self._fits:
            truth = np.concatenate([self.ci, self.ewif, self.wue], axis=1)
            idx = np.arange(h - self.fc["warmup_hours"] + 1, h + 1) % self.T
            self._fits[h] = holt_winters(truth[idx], self.fc)
        level, trend, season, sigma, last = self._fits[h]
        t_end = r.now_s + off[-1] + float(r.t.max())
        H = max(int(math.ceil(t_end / HOUR)) - h + 1,
                int(math.ceil(len(off) * self.fc["slot_s"] / HOUR)) + 1)
        damp = np.cumsum(self.fc["phi"] ** np.arange(1, H + 1))
        mean = (level[None, :] + damp[:, None] * trend[None, :]
                + season[np.arange(H) % self.fc["period"]])
        hi = mean + (self.fc["z90"] * sigma[None, :]
                     * np.sqrt(np.arange(1, H + 1))[:, None])
        return Forecast(h, mean, hi, last)


@dataclasses.dataclass
class Forecast:
    """Hourly forecast rows after ``issue_hour``, linear between them,
    held flat outside; ``anchor`` is the last observed row."""
    issue_hour: int
    mean: np.ndarray
    hi: np.ndarray
    anchor: np.ndarray

    def _integral(self, u, which):
        grid = np.vstack([self.anchor[None, :], getattr(self, which)])
        H = grid.shape[0] - 1
        cum = np.vstack([np.zeros((1, grid.shape[1])),
                         np.cumsum(0.5 * (grid[:-1] + grid[1:]), axis=0)])
        below = np.minimum(u, 0.0)[:, None] * grid[0][None, :]
        above = np.maximum(u - H, 0.0)[:, None] * grid[-1][None, :]
        uc = np.clip(u, 0.0, H)
        k = np.minimum(uc.astype(np.int64), H - 1)
        f = (uc - k)[:, None]
        return (below + above + cum[k] + grid[k] * f
                + 0.5 * (grid[k + 1] - grid[k]) * f ** 2)

    def mean_many(self, t0, t1, which):
        """Time-mean over each window [t0, t1] (seconds)."""
        u0 = np.asarray(t0, np.float64) / HOUR - self.issue_hour
        u1 = np.maximum(np.asarray(t1, np.float64) / HOUR - self.issue_hour,
                        u0 + 1e-9)
        return ((self._integral(u1, which) - self._integral(u0, which))
                / (u1 - u0)[:, None])


def holt_winters(y: np.ndarray, fc: dict):
    """Additive damped-trend seasonal filter (ETS(A,Ad,A)) over the hourly
    history ``y`` [T, C] for every (alpha, beta, gamma) of the grid; per
    column the triple with the least one-step squared error after the
    first period. Returns level, trend, season [period, C], the residual
    sigma and the last row."""
    y = np.asarray(y, np.float64)
    m, phi = fc["period"], fc["phi"]
    grid = np.array([(a, b, g) for a in fc["alphas"] for b in fc["betas"]
                     for g in fc["gammas"]])
    a, b_, g = (grid[:, k, None] for k in range(3))
    T, C = y.shape
    P = len(grid)
    level = np.tile(y[:m].mean(axis=0), (P, 1))
    trend = np.zeros((P, C))
    season = np.tile(y[:m] - y[:m].mean(axis=0), (P, 1, 1))
    sse = np.zeros((P, C))
    for k in range(T):
        s_prev = season[:, 0]
        err = y[k] - (level + phi * trend + s_prev)
        new_level = a * (y[k] - s_prev) + (1 - a) * (level + phi * trend)
        trend = b_ * (new_level - level) + (1 - b_) * phi * trend
        new_s = g * (y[k] - new_level) + (1 - g) * s_prev
        season = np.concatenate([season[:, 1:], new_s[:, None]], axis=1)
        level = new_level
        if k >= m:
            sse += err * err
    best = np.argmin(sse, axis=0)
    cols = np.arange(C)
    sigma = np.sqrt(sse[best, cols] / max(T - m, 1))
    return (level[best, cols], trend[best, cols],
            season[best, :, cols].T, sigma, y[-1].copy())


# ---------------------------------------------------------------------------
# The exact optimum and the comparison
# ---------------------------------------------------------------------------

def lp_optimum(cost: np.ndarray, allowed: np.ndarray,
               capacity: np.ndarray) -> Optional[float]:
    """Least total cost that gives every row one allowed column within the
    columns' capacities, or None where there is no such assignment."""
    M, C = cost.shape
    rows, cols = np.nonzero(allowed)
    n = rows.size
    var = np.arange(n)
    a_eq = sparse.csr_matrix((np.ones(n), (rows, var)), shape=(M, n))
    a_ub = sparse.csr_matrix((np.ones(n), (cols, var)), shape=(C, n))
    res = optimize.linprog(cost[rows, cols], A_ub=a_ub,
                           b_ub=np.asarray(capacity, np.float64),
                           A_eq=a_eq, b_eq=np.ones(M), bounds=(0, None),
                           method="highs")
    return float(res.fun) if res.status == 0 else None


def check_round(inst: Instance, r: Round) -> dict:
    """Exact counts for one round: hard or soft as the reference says
    (``mode``), jobs left without a column (``unassigned``), columns over
    capacity (``over_capacity``) and jobs on an arc the reference forbids
    beyond float32 rounding (``masked``)."""
    out = dict(mode=int(inst.soft != r.softened), unassigned=0,
               over_capacity=0, masked=0)
    if out["mode"]:
        return out
    a = r.assign
    C = inst.allowed.shape[1]
    ok = (a >= 0) & (a < C)
    out["unassigned"] = int((~ok).sum())
    used = np.bincount(a[ok], minlength=C)
    out["over_capacity"] = int((used > inst.capacity).sum())
    rows = np.nonzero(ok)[0]
    out["masked"] = int((inst.margin[rows, a[ok]]
                         < -inst.band[rows, a[ok]]).sum())
    return out


def gaps(inst: Instance, r: Round) -> Optional[dict]:
    """Relative gaps to the exact optimum, both priced on the reference's
    costs: of the served assignment (after the host's rounding and polish)
    and of the Sinkhorn plan's fractional objective (before it). None where
    the reference finds no assignment."""
    opt = lp_optimum(inst.cost, inst.allowed, inst.capacity)
    if opt is None or (r.assign < 0).any():
        return None
    served = float(inst.cost[np.arange(len(r.assign)), r.assign].sum())
    scale = max(abs(opt), 1e-9)
    out = dict(served_gap=(served - opt) / scale)
    # A round answered with no plan the reference can price fails the check.
    out["plan_gap"] = math.inf
    if r.plan is not None and r.plan.shape == inst.cost.shape:
        out["plan_gap"] = abs(float((r.plan * inst.cost).sum()) - opt) / scale
    return out


def sample_rounds(sizes: List[int], k: int, seed: int) -> List[int]:
    """Indices of ``k`` rounds drawn from the seed, the largest among them."""
    if not sizes:
        return []
    largest = int(np.argmax(sizes))
    rest = [i for i in range(len(sizes)) if i != largest]
    rng = np.random.default_rng([seed % (2 ** 63), 7])
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return sorted([largest] + [rest[i] for i in pick])

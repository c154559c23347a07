"""Holt–Winters damped-trend seasonal forecaster, fit with ``jax.lax.scan``.

The additive damped-trend seasonal recursions (Hyndman & Athanasopoulos §7.3,
the ETS(A,Ad,A) filter) over hourly history ``y[t]``:

    l_t = α·(y_t − s_{t−m}) + (1−α)·(l_{t−1} + φ·b_{t−1})
    b_t = β·(l_t − l_{t−1}) + (1−β)·φ·b_{t−1}
    s_t = γ·(y_t − l_t) + (1−γ)·s_{t−m}

"Fitting" here = one forward filter pass per candidate smoothing-parameter
triple, selecting the per-column triple with the lowest post-warmup one-step
SSE. The filter is a ``lax.scan`` over time, ``vmap``-ed over the parameter
grid, and jitted **once per history shape** — the scheduler refits every
simulated hour with a growing-but-bucketed window, so the same compiled
executable serves thousands of refits (the test suite pins the ≥10× second-
fit speedup).

Point forecasts are closed-form from the final state; quantile bands use the
selected triple's one-step residual σ widened with √horizon.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

import repro.obs as obs
from repro.forecast import base

obs.watch_jax()

# Candidate smoothing parameters (α, β, γ). A coarse grid is standard for
# online refitting: the SSE surface is flat near the optimum and the filter
# cost is P parallel scans, all fused into one compiled program.
_ALPHAS = (0.2, 0.4, 0.7)
_BETAS = (0.05, 0.15)
_GAMMAS = (0.1, 0.3)
PARAM_GRID = np.array([(a, b, g) for a in _ALPHAS for b in _BETAS
                       for g in _GAMMAS], np.float32)
PHI = 0.98        # trend damping (φ<1: long-horizon forecasts flatten out)

# History windows are clipped to at most MAX_FIT_PERIODS seasonal periods and
# padded up to the next bucket (a small set of whole-period multiples) so the
# jitted filter compiles for a handful of shapes, not one per simulated hour.
# Padding prepends a cyclic extension of the oldest period, which keeps the
# seasonal phase of the padded series identical to the real one.
FIT_BUCKET_PERIODS = (2, 3, 4, 6, 8, 12, 14)
MAX_FIT_PERIODS = FIT_BUCKET_PERIODS[-1]


def fit_bucket_for(rows: int, period: int) -> int:
    """Smallest whole-period bucket ≥ rows."""
    for k in FIT_BUCKET_PERIODS:
        if rows <= k * period:
            return k * period
    return MAX_FIT_PERIODS * period


def _hw_filter_impl(y: jnp.ndarray, params: jnp.ndarray, valid0: jnp.ndarray,
                    period: int):
    """Forward ETS(A,Ad,A) filter over ``y`` for every parameter triple.

    Args:
      y: [T, C] history (oldest first).
      params: [P, 3] (α, β, γ) candidates.
      valid0: scalar int — rows before this index are padding replicas of the
        oldest observation; their one-step errors are excluded from the SSE.
      period: seasonal period (static → part of the compile key).

    Returns:
      level [P, C], trend [P, C], season [P, period, C] (season[0] is the
      seasonal term for the *next* time step), sse [P, C], count [].
    """
    T, C = y.shape
    l0 = jnp.mean(y[:period], axis=0)                         # [C]
    s0 = y[:period] - l0[None, :]                             # [period, C]
    b0 = jnp.zeros((C,), y.dtype)
    warmup = valid0 + period

    def one(abg):
        alpha, beta, gamma = abg[0], abg[1], abg[2]

        def step(carry, inp):
            l, b, s, sse, cnt = carry
            y_t, t = inp
            s_prev = s[0]
            yhat = l + PHI * b + s_prev
            err = y_t - yhat
            l_new = alpha * (y_t - s_prev) + (1 - alpha) * (l + PHI * b)
            b_new = beta * (l_new - l) + (1 - beta) * PHI * b
            s_new = gamma * (y_t - l_new) + (1 - gamma) * s_prev
            s = jnp.concatenate([s[1:], s_new[None, :]], axis=0)
            use = (t >= warmup).astype(y.dtype)
            return (l_new, b_new, s, sse + use * err * err, cnt + use), None

        init = (l0, b0, s0, jnp.zeros((C,), y.dtype), jnp.zeros((), y.dtype))
        (l, b, s, sse, cnt), _ = jax.lax.scan(
            step, init, (y, jnp.arange(T, dtype=y.dtype)))
        return l, b, s, sse, cnt

    return jax.vmap(one)(params)


_hw_filter = functools.partial(jax.jit, static_argnames=("period",))(
    _hw_filter_impl)


def damped_sum(horizon: int, phi: float = PHI) -> np.ndarray:
    """[Σ_{i=1..h} φ^i for h=1..H] — the damped-trend forecast multiplier."""
    return np.cumsum(phi ** np.arange(1, horizon + 1))


@base.register_model
class HoltWinters(base.Forecaster):
    """Damped-trend seasonal Holt–Winters with grid-selected smoothing."""

    name = "holtwinters"
    on_device = True
    description = ("damped-trend seasonal ETS(A,Ad,A) filter on "
                   "jax.lax.scan, grid-selected smoothing, jitted once "
                   "per padded history shape")

    def __init__(self, period: int = 24):
        self.period = period

    def fit(self, history: np.ndarray) -> "HoltWinters":
        """Refit on ``history`` [hours, columns]: span ``forecast.fit``."""
        with obs.span("forecast.fit", hours=int(np.shape(history)[0]),
                      columns=int(np.shape(history)[1])):
            return self._fit(history)

    def _fit(self, history: np.ndarray) -> "HoltWinters":
        y = np.asarray(history, np.float64)
        self._T = y.shape[0]
        self._last = y[-1]
        # Too short for a seasonal init: delegate (which itself falls back to
        # persistence below one full period).
        if self._T < 2 * self.period:
            self._fallback = base.SeasonalNaive(self.period).fit(y)
            return self
        self._fallback = None
        y = y[-MAX_FIT_PERIODS * self.period:]
        rows = y.shape[0]
        pad = fit_bucket_for(rows, self.period) - rows
        if pad:
            # Cyclic extension of the oldest period, aligned so the row just
            # before y[0] is y[period-1]: the padded series is exactly
            # periodic, preserving seasonal phase and init.
            reps = int(np.ceil(pad / self.period))
            ext = np.tile(y[:self.period], (reps, 1))[-pad:] \
                if pad % self.period == 0 else \
                np.tile(y[:self.period], (reps + 1, 1))[
                    self.period - (pad % self.period):][:pad]
            y = np.vstack([ext, y])
        level, trend, season, sse, cnt = _hw_filter(
            jnp.asarray(y, jnp.float32), jnp.asarray(PARAM_GRID),
            jnp.asarray(pad, jnp.float32), self.period)
        level, trend = np.asarray(level), np.asarray(trend)
        season, sse = np.asarray(season), np.asarray(sse)
        best = np.argmin(sse, axis=0)                      # [C]
        cols = np.arange(y.shape[1])
        self._level = level[best, cols].astype(np.float64)
        self._trend = trend[best, cols].astype(np.float64)
        self._season = season[best, :, cols].T.astype(np.float64)  # [m, C]
        n = max(float(np.asarray(cnt)[0]), 1.0)
        self._sigma = np.sqrt(sse[best, cols].astype(np.float64) / n)
        return self

    def predict(self, horizon: int) -> base.Forecast:
        if self._fallback is not None:
            return self._fallback.predict(horizon)
        damp = damped_sum(horizon)
        idx = np.arange(horizon) % self.period
        mean = (self._level[None, :] + damp[:, None] * self._trend[None, :]
                + self._season[idx])
        lo, hi = self._gaussian_band(mean, self._sigma)
        return base.Forecast(self._T - 1, mean, lo, hi, self._last.copy())

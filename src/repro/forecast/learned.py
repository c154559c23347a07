"""Learned telemetry forecaster: an RG-LRU sequence head with quantile
outputs, trained on sliding telemetry windows.

This is the point where the scheduling side of the repo finally exercises
the model stack: the recurrent core is the Griffin recurrent block from
``repro.models.rglru`` (conv1d → RG-LRU → gated output projection), the
optimizer is ``repro.optim.adamw``, checkpoints go through
``repro.checkpoint.store``, and the linear recurrence can optionally run
through the Pallas kernel (``repro.kernels.rglru_scan``) instead of the XLA
associative scan.

Model shape
-----------
Each history column (one region × signal series) is treated as an
independent univariate sample: the network consumes a normalized window of
the last ``window`` hours and emits, for each of the next ``horizon``
hours, three quantile *residuals* (q10 / q50 / q90) **on top of the
seasonal-naive continuation of the window**. The output head is
zero-initialized, so an untrained ``learned`` forecaster is *exactly*
seasonal-naive — training can only move it away from the strongest cheap
baseline, which is what makes the walk-forward comparison in the tests
stable under a fixed seed.

Fit / refit protocol
--------------------
``fit(history)`` trains on every sliding window of the history the first
time it is called (and again after ``retrain_every`` subsequent fits —
the walk-forward refit cadence), then *conditions* on the tail window to
produce forecasts. ``update(history)`` never retrains: it re-conditions on
the new tail with the existing parameters (trains only when none exist),
which is what ``forecast.backtest(..., refit_every=K)`` calls between full
refits. Histories too short to train or condition fall back to
seasonal-naive, mirroring ``HoltWinters``.

The train step is jitted once per (batch, window, horizon) shape and the
per-column inference pass is batched over columns (the vmap dimension),
padded to a column bucket and jitted once per padded shape — the same
compile-amortization discipline as the Holt–Winters grid filter.
"""
from __future__ import annotations

import functools
import json
import os
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

import repro.obs as obs

from repro.checkpoint import store
from repro.forecast import base
from repro.models import common, rglru
from repro.models.ssm import _causal_conv
from repro.optim import adamw as _adamw
from repro.optim import cosine_schedule

#: Quantile levels of the three output heads (the middle one is the point
#: forecast; the outer pair matches the 10/90 band every forecaster emits).
TRAIN_QUANTILES = (0.1, 0.5, 0.9)

#: Columns are padded to a multiple of this for the jitted inference pass,
#: so different region counts reuse a handful of compiled shapes.
COLUMN_BUCKET = 8

_D_CONV = 4


# ---------------------------------------------------------------------------
# Network
# ---------------------------------------------------------------------------

def init_params(key, d_model: int, horizon: int):
    """Parameter tree: 2-feature embed → Griffin recurrent block → quantile
    head. The head is zero-initialized (output = seasonal-naive residual 0,
    so the untrained model *is* seasonal-naive) and the causal conv starts
    as the identity tap so the recurrence sees the embedded series from
    step one. The head reads ``[h_T | a_T]`` — final recurrent state plus
    the final seasonal anomaly — so the strongest known residual structure
    (anomaly persistence) is one weight away from the init."""
    ks = jax.random.split(key, 2)
    tree = dict(
        inp=common.dense_init(ks[0], (2, d_model), ("embed", "mlp"),
                              jnp.float32, fan_in=2),
        inp_b=common.zeros_init((d_model,), ("mlp",), jnp.float32),
        block=rglru.block_init(ks[1], d_model, lru_width=d_model,
                               d_conv=_D_CONV),
        norm=common.zeros_init((d_model,), ("embed_nosplit",), jnp.float32),
        head=common.zeros_init((d_model + 1,
                                horizon * len(TRAIN_QUANTILES)),
                               ("mlp", "embed"), jnp.float32),
        head_b=common.zeros_init((horizon * len(TRAIN_QUANTILES),),
                                 ("embed",), jnp.float32),
    )
    params, _ = common.split_tree(tree)
    params["block"]["conv_w"] = params["block"]["conv_w"].at[-1].set(1.0)
    # Outer-quantile biases start at ∓0.25σ so the untrained band has
    # width (the q50 point forecast stays exactly seasonal-naive); training
    # calibrates both tails via the pinball loss.
    hb = params["head_b"].reshape(horizon, len(TRAIN_QUANTILES))
    hb = hb.at[:, 0].set(-0.25).at[:, -1].set(0.25)
    params["head_b"] = hb.reshape(-1)
    return params


def _recurrent_block(x, p, scan_impl: str):
    """Griffin recurrent block with a pluggable linear recurrence: the
    default delegates straight to ``models.rglru.block_apply`` (train
    path, XLA associative scan); ``pallas`` swaps only the scan for the
    ``repro.kernels.rglru_scan`` kernel (interpret mode off-TPU), keeping
    everything around it identical."""
    if scan_impl != "pallas":
        return rglru.block_apply(x, p)[0]
    gate = jax.nn.gelu(x @ p["in_gate"])
    u = x @ p["in_x"]
    u, _ = _causal_conv(u, p["conv_w"], p["conv_b"])
    a, bx = rglru._gates(u, p)
    from repro.kernels.rglru_scan.ops import rglru_scan as kernel_scan
    y = kernel_scan(a, bx).astype(u.dtype)
    return (y * gate) @ p["out"]


def _quantiles_from_windows(params, xw, horizon: int, period: int,
                            scan_impl: str):
    """xw: [B, L] normalized windows → [B, horizon, Q] quantile forecasts
    = seasonal-naive continuation of each window + learned residuals.

    Per-step input features: the value and its seasonal anomaly (lag-period
    delta, zero over the first period) — the anomaly series carries the
    synoptic (multi-day) component the seasonal base is blind to.
    """
    B, L = xw.shape
    anom = jnp.concatenate(
        [jnp.zeros((B, period)), xw[:, period:] - xw[:, :-period]], axis=1)
    feats = jnp.stack([xw, anom], axis=-1)                       # [B, L, 2]
    h = feats @ params["inp"] + params["inp_b"]                  # [B, L, D]
    h = h + _recurrent_block(h, params["block"], scan_impl)
    h = common.rms_norm(h, params["norm"])
    head_in = jnp.concatenate([h[:, -1], anom[:, -1:]], axis=-1)
    out = head_in @ params["head"] + params["head_b"]
    deltas = out.reshape(B, horizon, len(TRAIN_QUANTILES))
    idx = (L - period) + (jnp.arange(horizon) % period)
    base_rows = xw[:, idx]                                       # [B, H]
    return base_rows[..., None] + deltas


def _pinball(q, y):
    """Mean pinball loss of the three quantile heads. q: [B, H, Q],
    y: [B, H]."""
    levels = jnp.asarray(TRAIN_QUANTILES, jnp.float32)
    d = y[..., None] - q
    return jnp.mean(jnp.maximum(levels * d, (levels - 1.0) * d))


#: Bound on the per-config jitted train/infer caches below. Sweeps iterate
#: over many forecaster configs in one process; an unbounded cache pins
#: every config's compiled executables (and their device buffers) for the
#: process lifetime. LRU-evicting a config merely costs a retrace if it
#: comes back.
CACHE_CONFIGS = 32

#: Factory-build counters: each build is one fresh set of jit compilations
#: (a cache miss OR a re-build after LRU eviction), so ``builds − misses``
#: counts evictions and ``builds`` counts retraces. Read via
#: :func:`cache_stats` (the perf harness reports these); every build also
#: bumps the shared ``jit/builds/*`` counters in ``repro.obs``, so traced
#: runs fold retrace accounting into the same snapshot as everything else.
_BUILDS = {"train_step": 0, "predict_fn": 0}


def cache_stats() -> dict:
    """Cache/retrace accounting for the perf harness: per-cache lru stats
    (hits/misses/currsize/maxsize) plus total factory builds (== jit
    retrace sets, counting rebuilds after eviction)."""
    out = {}
    for name, fn in (("train_step", _train_step),
                     ("predict_fn", _predict_fn)):
        info = fn.cache_info()
        out[name] = dict(hits=info.hits, misses=info.misses,
                         currsize=info.currsize, maxsize=info.maxsize,
                         builds=_BUILDS[name])
    return out


@functools.lru_cache(maxsize=CACHE_CONFIGS)
def _train_step(horizon: int, period: int, scan_impl: str, lr: float,
                weight_decay: float, train_steps: int):
    """(optimizer, jitted step) — cached per config so refits and multiple
    forecaster instances share one compiled executable per batch shape."""
    _BUILDS["train_step"] += 1
    obs.counter("jit/builds/train_step")
    opt = _adamw(
        lr=cosine_schedule(lr, max(train_steps // 10, 1),
                           max(train_steps, 1)),
        weight_decay=weight_decay)

    def loss_fn(params, xb, yb):
        return _pinball(
            _quantiles_from_windows(params, xb, horizon, period, scan_impl),
            yb)

    @jax.jit
    def step(params, state, xb, yb):
        loss, grads = jax.value_and_grad(loss_fn)(params, xb, yb)
        new_params, new_state, _ = opt.update(grads, state, params)
        return new_params, new_state, loss

    return opt, step, jax.jit(loss_fn)


@functools.lru_cache(maxsize=CACHE_CONFIGS)
def _predict_fn(horizon: int, period: int, scan_impl: str):
    """Jitted batched (per-column) inference, compiled once per padded
    [columns, window] shape."""
    _BUILDS["predict_fn"] += 1
    obs.counter("jit/builds/predict_fn")
    @jax.jit
    def run(params, xw):
        return _quantiles_from_windows(params, xw, horizon, period,
                                       scan_impl)
    return run


# ---------------------------------------------------------------------------
# The forecaster
# ---------------------------------------------------------------------------

@base.register_model
class LearnedForecaster(base.Forecaster):
    """RG-LRU sequence head over sliding telemetry windows with quantile
    outputs (residual over seasonal-naive; zero-init == seasonal-naive)."""

    name = "learned"
    on_device = True
    description = ("RG-LRU (Griffin) sequence head with q10/q50/q90 "
                   "outputs, trained on sliding telemetry windows as a "
                   "residual over seasonal-naive")

    def __init__(self, period: int = 24, window: int = 48,
                 horizon: int = 24, d_model: int = 16,
                 train_steps: int = 300, batch: int = 64,
                 lr: float = 1e-3, weight_decay: float = 0.1,
                 retrain_every: int = 24, seed: int = 0,
                 scan_impl: str = "assoc", checkpoint: str = ""):
        """Args:
          period: seasonal period (hours) of the residual base.
          window: conditioning window length (hours); must cover ≥ 1 period.
          horizon: trained lead hours; longer ``predict`` horizons extend
            periodically.
          d_model: embed width == RG-LRU width.
          train_steps / batch / lr / weight_decay: training-loop knobs
            (``repro.optim.adamw`` with cosine schedule + global-norm clip).
          retrain_every: retrain after this many subsequent ``fit`` calls
            (the walk-forward refit cadence; 0 = train once, never again).
          seed: PRNG seed for init and batch sampling (fully deterministic).
          scan_impl: linear-recurrence implementation for BOTH training and
            inference — ``assoc`` (XLA associative scan) or ``pallas`` (the
            ``repro.kernels.rglru_scan`` kernel; interpret mode off-TPU).
            The kernel is differentiable via its custom VJP, so training
            runs through it too.
          checkpoint: optional directory saved by :meth:`save` — restores
            the trained parameters (and their config) at construction.
        """
        if window < period:
            raise ValueError(f"window ({window}) must cover at least one "
                             f"period ({period})")
        if scan_impl not in ("assoc", "pallas"):
            raise ValueError(f"scan_impl must be 'assoc' or 'pallas', "
                             f"got {scan_impl!r}")
        self.period = int(period)
        self.window = int(window)
        self.horizon = int(horizon)
        self.d_model = int(d_model)
        self.train_steps = int(train_steps)
        self.batch = int(batch)
        self.lr = float(lr)
        self.weight_decay = float(weight_decay)
        self.retrain_every = int(retrain_every)
        self.seed = int(seed)
        self.scan_impl = scan_impl
        self._params = None
        self._mu: Optional[np.ndarray] = None
        self._sd: Optional[np.ndarray] = None
        self._fallback: Optional[base.Forecaster] = None
        self._fits_since_train = 0
        self.train_count = 0          # full training runs so far
        self.train_seconds = 0.0      # wall time spent training
        self.last_loss = float("nan")
        if checkpoint:
            self._restore(checkpoint)

    # -- fit / update --------------------------------------------------------

    def fit(self, history: np.ndarray) -> "LearnedForecaster":
        """Walk-forward entry point: trains on the first call (and again
        every ``retrain_every`` fits), then conditions on the tail window."""
        return self._ingest(np.asarray(history, np.float64),
                            allow_train=True)

    def update(self, history: np.ndarray) -> "LearnedForecaster":
        """Cheap walk-forward refresh: re-condition on the new tail without
        retraining (trains only if no trained parameters exist yet)."""
        return self._ingest(np.asarray(history, np.float64),
                            allow_train=False)

    def _ingest(self, y: np.ndarray, allow_train: bool) -> "LearnedForecaster":
        assert y.ndim == 2 and y.shape[0] >= 1
        self._T = y.shape[0]
        self._last = y[-1].copy()
        can_condition = self._T >= max(self.window, self.period + 1)
        can_train = self._T >= self.window + self.horizon + 4
        wrong_cols = (self._params is not None
                      and y.shape[1] != self._mu.shape[0])
        if self._params is None or wrong_cols:
            if not can_train:
                obs.warn("forecast.fallback_seasonal_naive",
                         f"history of {self._T} hours is below the "
                         f"{self.window + self.horizon + 4}-hour training "
                         "minimum; serving seasonal-naive instead")
                self._fallback = base.SeasonalNaive(self.period).fit(y)
                return self
            self._train(y)
        elif allow_train:
            # Only fit() calls advance the retrain cadence — update() is
            # documented to never retrain and never count toward it.
            self._fits_since_train += 1
            if (can_train and self.retrain_every > 0
                    and self._fits_since_train >= self.retrain_every):
                self._train(y)
        if not can_condition:
            self._fallback = base.SeasonalNaive(self.period).fit(y)
            return self
        self._fallback = None
        self._condition(y)
        return self

    # -- training ------------------------------------------------------------

    def _train(self, y: np.ndarray) -> None:
        with obs.timed("forecast.fit", hours=int(y.shape[0]),
                       columns=int(y.shape[1]),
                       train_steps=self.train_steps) as t:
            self._train_impl(y)
            t.set(loss=self.last_loss)
        self.train_seconds += t.elapsed_s

    def _train_impl(self, y: np.ndarray) -> None:
        self._mu = y.mean(axis=0)
        self._sd = np.maximum(y.std(axis=0), 1e-9)
        z = (y - self._mu) / self._sd                           # [T, C]
        L, H = self.window, self.horizon
        n_origins = z.shape[0] - L - H + 1
        X = np.stack([z[o:o + L] for o in range(n_origins)])    # [n, L, C]
        Y = np.stack([z[o + L:o + L + H] for o in range(n_origins)])
        # Hold out the most recent ~20% of window origins (all columns) as
        # a validation fold: the returned parameters are the best-on-val
        # snapshot of the trajectory, *including the seasonal-naive init* —
        # so on histories too short to generalize from, training can only
        # tie the baseline, never silently regress far below it.
        n_val = int(round(0.2 * n_origins)) if n_origins >= 5 else 0
        n_tr = n_origins - n_val

        def flat(a):
            return np.ascontiguousarray(
                a.transpose(0, 2, 1)).reshape(-1, a.shape[1])

        Xtr, Ytr = flat(X[:n_tr]), flat(Y[:n_tr])
        params = init_params(jax.random.PRNGKey(self.seed), self.d_model, H)
        # Training runs whatever recurrence the config selects: the Pallas
        # kernel carries a custom VJP (its backward pass is one more kernel
        # scan on reversed time — see kernels/rglru_scan/ops.py), with
        # gradient parity against the associative scan pinned in tests.
        opt, step, val_loss = _train_step(
            H, self.period, self.scan_impl, self.lr, self.weight_decay,
            self.train_steps)
        state = opt.init(params)
        rng = np.random.default_rng(self.seed)
        N = Xtr.shape[0]
        B = min(self.batch, N)
        if n_val:
            Xva = jnp.asarray(flat(X[n_tr:]), jnp.float32)
            Yva = jnp.asarray(flat(Y[n_tr:]), jnp.float32)
            best = (float(val_loss(params, Xva, Yva)), params)
        loss = np.nan
        eval_every = 10
        for s in range(self.train_steps):
            idx = rng.integers(0, N, size=B)
            params, state, loss = step(
                params, state, jnp.asarray(Xtr[idx], jnp.float32),
                jnp.asarray(Ytr[idx], jnp.float32))
            if n_val and (s % eval_every == eval_every - 1
                          or s == self.train_steps - 1):
                v = float(val_loss(params, Xva, Yva))
                if v < best[0]:
                    best = (v, params)
        self._params = best[1] if n_val else params
        self.last_loss = float(loss)
        self._fits_since_train = 0
        self.train_count += 1

    # -- conditioning + prediction -------------------------------------------

    def _condition(self, y: np.ndarray) -> None:
        """Run the (jitted, column-batched) inference pass on the tail
        window; caches the denormalized [H, C, Q] quantile tensor."""
        with obs.span("forecast.infer", columns=int(y.shape[1])):
            z = (y[-self.window:] - self._mu) / self._sd
            xw = np.ascontiguousarray(z.T)                      # [C, L]
            C = xw.shape[0]
            Cp = -(-C // COLUMN_BUCKET) * COLUMN_BUCKET
            if Cp > C:
                xw = np.vstack([xw, np.zeros((Cp - C, self.window))])
            run = _predict_fn(self.horizon, self.period, self.scan_impl)
            q = np.asarray(run(self._params, jnp.asarray(xw, jnp.float32)),
                           np.float64)[:C]                      # [C, H, Q]
        q = np.sort(q, axis=-1)        # enforce q10 ≤ q50 ≤ q90 pointwise
        q = q.transpose(1, 0, 2)                                # [H, C, Q]
        self._q = q * self._sd[None, :, None] + self._mu[None, :, None]

    def predict(self, horizon: int) -> base.Forecast:
        if self._fallback is not None:
            return self._fallback.predict(horizon)
        q = self._q
        H = q.shape[0]
        if horizon > H:
            extra = np.arange(H, horizon)
            if H >= self.period:      # extend periodically from the tail
                idx = H - self.period + (extra - H) % self.period
            else:                     # degenerate config: hold the last row
                idx = np.full(extra.shape, H - 1)
            q = np.concatenate([q, q[idx]], axis=0)
        q = q[:horizon]
        return base.Forecast(self._T - 1, q[..., 1], q[..., 0], q[..., 2],
                             self._last.copy())

    # -- checkpointing -------------------------------------------------------

    def save(self, directory: str, step: int = 0) -> str:
        """Persist the trained parameters + normalization through
        ``repro.checkpoint.store`` (atomic commit); the manifest carries the
        model config so :meth:`load` reconstructs without arguments."""
        if self._params is None:
            raise ValueError("nothing to save: forecaster has not trained")
        tree = dict(params=self._params, mu=np.asarray(self._mu),
                    sd=np.asarray(self._sd))
        extra = dict(kind="learned-forecaster", config=self._config())
        return store.save_checkpoint(directory, step, tree, extra)

    def _config(self) -> dict:
        return dict(period=self.period, window=self.window,
                    horizon=self.horizon, d_model=self.d_model,
                    scan_impl=self.scan_impl,
                    n_columns=int(self._mu.shape[0]))

    def _restore(self, directory: str, step: Optional[int] = None) -> None:
        step = store.latest_step(directory) if step is None else step
        if step is None:
            raise FileNotFoundError(f"no checkpoint under {directory!r}")
        with open(os.path.join(directory, f"step-{step}",
                               "manifest.json")) as f:
            cfg = json.load(f)["config"]
        n_cols = cfg.pop("n_columns")
        for k, v in cfg.items():
            setattr(self, k, v)
        target = dict(
            params=init_params(jax.random.PRNGKey(0), self.d_model,
                               self.horizon),
            mu=np.zeros(n_cols), sd=np.ones(n_cols))
        tree = store.restore_checkpoint(directory, step, target)
        self._params = tree["params"]
        self._mu = np.asarray(tree["mu"], np.float64)
        self._sd = np.asarray(tree["sd"], np.float64)
        self._fits_since_train = 0

    @classmethod
    def load(cls, directory: str, step: Optional[int] = None
             ) -> "LearnedForecaster":
        """Reconstruct a trained forecaster from a :meth:`save` directory
        (config from the manifest; call ``update(history)`` to condition)."""
        f = cls()
        f._restore(directory, step)
        return f

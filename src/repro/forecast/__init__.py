"""Forecasting + temporal-shifting subsystem.

``base``        Forecaster interface + registry (did-you-mean errors,
                introspected param schemas), persistence / seasonal-naive
                baselines, the true-future Oracle, and the error-injection
                Perturbed wrapper (the ``forecast_error`` scenario regime).
``holtwinters`` Damped-trend seasonal Holt–Winters fit with ``jax.lax.scan``,
                jitted once per history shape.
``learned``     Learned forecaster: RG-LRU (Griffin) sequence head from
                ``repro.models.rglru`` with q10/q50/q90 quantile outputs,
                trained on sliding telemetry windows via ``repro.optim
                .adamw``, checkpointed through ``repro.checkpoint.store``.
``backtest``    Walk-forward MAPE / pinball-loss / coverage scoring against
                telemetry series, with a fit/refit cadence for models whose
                training is expensive.
``planner``     Spatio-temporal (regions × horizon-slots) assignment builder
                + the deferral queue used by the forecast pipeline.
"""
from repro.forecast import holtwinters as _holtwinters  # registers the model
from repro.forecast import learned as _learned          # registers the model
from repro.forecast.backtest import (backtest, backtest_telemetry, mape,
                                     pinball_loss)
from repro.forecast.base import (Forecast, Forecaster, Oracle, Persistence,
                                 Perturbed, SeasonalNaive,
                                 describe_forecasters, forecaster_schema,
                                 list_forecasters, make_forecaster,
                                 on_device)
from repro.forecast.holtwinters import HoltWinters
from repro.forecast.learned import LearnedForecaster
from repro.forecast.planner import DeferralQueue, TemporalPlan, \
    build_temporal_plan

__all__ = [
    "Forecast", "Forecaster", "Persistence", "SeasonalNaive", "Oracle",
    "Perturbed", "HoltWinters", "LearnedForecaster", "make_forecaster",
    "list_forecasters", "forecaster_schema", "describe_forecasters",
    "on_device",
    "backtest", "backtest_telemetry", "mape", "pinball_loss",
    "DeferralQueue", "TemporalPlan", "build_temporal_plan",
]

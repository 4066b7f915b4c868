"""The engine: one entry point that runs every registered strategy.

    from repro.api import Experiment, run

    result = run(Experiment(model=model, client_iters=iters, fed=fed,
                            strategy="fedelmy", eval_fn=acc))
    result.params            # final global model (pytree)
    result.clients[0].models # per-pool-model records
    result.final_metric      # eval_fn(final params)

or, with keyword convenience: ``run(model=model, client_iters=iters,
fed=fed, strategy="fedseq")``.
"""
from __future__ import annotations

import dataclasses
import time
import warnings
from typing import Any, Callable, Dict, Optional, Sequence

import jax

from repro import obs
from repro.api.results import RunResult
from repro.api.strategies import get_strategy_spec
from repro.configs.base import FedConfig

PyTree = Any


@dataclasses.dataclass
class Callbacks:
    """Uniform hooks every strategy honors where it applies: eval,
    logging and checkpointing plug in here instead of forking drivers.

    on_model_end(record: ModelRecord, params)   — after each pool model
    on_client_end(record: ClientRecord | RoundRecord, params)
                                                — after each client / round
    """
    on_model_end: Optional[Callable] = None
    on_client_end: Optional[Callable] = None


@dataclasses.dataclass
class Experiment:
    """A fully-specified federated run. `strategy` names a registered
    strategy; `fed.pool_backend` names a registered pool representation.

    `client_iters` entries are per-client infinite batch streams: either
    plain iterators (`repro.data.batch_iterator`) or device-resident
    `repro.data.DataPlan`s — scan-routed plan visits execute as one
    compiled program per local phase for every model family (DESIGN.md
    §9) with bit-identical results; custom-step blocks, callback runs
    and `scan=False` plans (a per-step oracle/debug knob) consume the
    same cursor via the per-step path."""
    model: Any                        # repro.models.Model (init/loss_fn/...)
    client_iters: Sequence[Any]       # per-client streams (see docstring)
    fed: FedConfig
    strategy: str = "fedelmy"
    key: Optional[jax.Array] = None   # default: PRNGKey(fed.seed)
    eval_fn: Optional[Callable] = None
    order: Optional[Sequence[int]] = None   # client visit order
    init_params: Optional[PyTree] = None    # skip model.init
    shots: int = 1                    # T for few-shot strategies
    strategy_options: Dict[str, Any] = dataclasses.field(default_factory=dict)
    callbacks: Callbacks = dataclasses.field(default_factory=Callbacks)

    def resolved_key(self) -> jax.Array:
        return (self.key if self.key is not None
                else jax.random.PRNGKey(self.fed.seed))

    def resolved_order(self) -> list:
        return (list(self.order) if self.order is not None
                else list(range(len(self.client_iters))))


def warn_unsupported_fields(experiment: Experiment) -> None:
    """Warn when an optional Experiment field is set that the strategy
    does not honor (shared by `run` and `run_batch`)."""
    spec = get_strategy_spec(experiment.strategy)
    for field, is_set in (("init_params", experiment.init_params is not None),
                          ("order", experiment.order is not None),
                          ("shots", experiment.shots != 1)):
        if is_set and field not in spec.supports:
            warnings.warn(
                f"strategy {experiment.strategy!r} ignores "
                f"Experiment.{field}; it honors "
                f"{sorted(spec.supports) or 'no optional fields'}",
                UserWarning, stacklevel=3)


def finalize_result(experiment: Experiment, out, wall_time_s: float,
                    ) -> RunResult:
    """Wrap a StrategyOutput into a RunResult: final-metric resolution plus
    timing (shared by `run` and the batched executors)."""
    final = None
    if experiment.eval_fn is not None:
        # Sequential strategies already evaluated the final params as the
        # last record's global_metric — reuse it instead of a second pass
        # over the held-out set.
        last = out.rounds[-1] if out.rounds else \
            out.clients[-1] if out.clients else None
        final = (last.global_metric
                 if last is not None and last.global_metric is not None
                 else float(experiment.eval_fn(out.params)))
    return RunResult(
        strategy=experiment.strategy,
        params=out.params,
        fed=experiment.fed,
        clients=out.clients,
        rounds=out.rounds,
        final_metric=final,
        wall_time_s=wall_time_s,
        final_pool=out.final_pool)


def _run(experiment: Optional[Experiment] = None, **kwargs) -> RunResult:
    """Execute an Experiment through the strategy registry and return a
    typed RunResult. Accepts either an Experiment or its fields as
    keyword arguments. (Implementation behind `repro.api.launch`; the
    public `run` is its deprecated alias.)"""
    if experiment is None:
        experiment = Experiment(**kwargs)
    elif kwargs:
        experiment = dataclasses.replace(experiment, **kwargs)
    spec = get_strategy_spec(experiment.strategy)
    warn_unsupported_fields(experiment)
    t0 = time.time()
    # For plan strategies `fn` is the sequential interpreter backend bound
    # to the registered plan (register_plan); opaque callables run as-is.
    with obs.span(obs.LAUNCH):
        out = spec.fn(experiment)
        return finalize_result(experiment, out, time.time() - t0)


def run(experiment: Optional[Experiment] = None, **kwargs) -> RunResult:
    """Deprecated: use ``repro.api.launch(experiment)`` — one front door
    for single runs, sweeps, scenarios and fleets. Bit-identical to it on
    the same Experiment (launch dispatches here)."""
    warnings.warn(
        "repro.api.run is deprecated; use repro.api.launch(experiment)",
        DeprecationWarning, stacklevel=2)
    return _run(experiment, **kwargs)

from .client import local_train, local_gradient
from .round import (clustered_update_step, resolve_aggregator,
                    stack_global_params)
from .workloads import (Workload, get_workload, lm_workload, register_workload,
                        registered_workloads)
from .loop import run_fl, run_fl_host, FLHistory, success_rate
from .sharded import exchange_bytes_per_device, make_sharded_fl_round
from .sim import (GridResult, grid_arrays, make_trial_fn, run_grid, simulate,
                  stack_case_plans, strategy_id)
from .experiment import (ExperimentResult, ExperimentSpec, LoweredScenario,
                         ScenarioSpec, TransformSpec, availability, engines,
                         quantity, register_engine, register_transform,
                         registered_transforms, run)
from .population import (default_num_blocks, derive_arrival_schedule,
                         make_async_trial_fn, make_hier_trial_fn,
                         make_population_round, staleness_weight,
                         streamed_selection, synthetic_population_plan)
from repro.core import (Aggregator, register_aggregator,
                        registered_aggregators, register_strategy,
                        registered_strategies)

__all__ = ["local_train", "local_gradient", "run_fl",
           "clustered_update_step", "resolve_aggregator",
           "stack_global_params", "Aggregator", "register_aggregator",
           "registered_aggregators",
           "run_fl_host", "FLHistory", "success_rate",
           "Workload", "get_workload", "lm_workload", "register_workload",
           "registered_workloads",
           "exchange_bytes_per_device", "make_sharded_fl_round",
           "GridResult", "grid_arrays", "make_trial_fn", "run_grid",
           "simulate", "stack_case_plans", "strategy_id",
           "ExperimentResult", "ExperimentSpec", "LoweredScenario",
           "ScenarioSpec", "TransformSpec", "availability", "engines",
           "quantity", "register_engine", "register_transform",
           "registered_transforms", "run",
           "register_strategy", "registered_strategies",
           "default_num_blocks", "derive_arrival_schedule",
           "make_async_trial_fn", "make_hier_trial_fn",
           "make_population_round", "staleness_weight", "streamed_selection",
           "synthetic_population_plan",
           # legacy alias served by __getattr__ below; listing it here keeps
           # `from repro.fl import *` providing it (star-import reads __all__)
           "ENGINE_STRATEGIES"]


def __getattr__(name: str):
    # Back-compat: the frozen ENGINE_STRATEGIES tuple is now a live view of
    # the append-only strategy registry (ids 0..6 unchanged, extensions
    # append).  Prefer registered_strategies().
    if name == "ENGINE_STRATEGIES":
        return registered_strategies()
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")

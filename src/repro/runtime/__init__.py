"""Training/serving runtime: step builders, Trainer, fault tolerance."""
from .fault import (FailureInjector, SimulatedFailure, StragglerWatchdog,
                    StepTimer)
from .train import Trainer, TrainerConfig, TrainState, dp_num_workers
from .serve import (build_cached_prefill, build_prefill, build_serve_step,
                    serve_shardings)

__all__ = [
    "FailureInjector", "SimulatedFailure", "StragglerWatchdog", "StepTimer",
    "Trainer", "TrainerConfig", "TrainState", "dp_num_workers",
    "build_cached_prefill", "build_prefill", "build_serve_step",
    "serve_shardings",
]

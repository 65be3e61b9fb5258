from .fault_tolerance import (ElasticPlan, EngineFailureInjector,
                              FailureInjector, SimulatedFailure,
                              StragglerMonitor, TrainLoop, TrainLoopConfig,
                              TrusteeFailure, delegation_elastic_plan)
from .compile_cache import enable_compile_cache

from .planner import PipelinePlan, group_profile, plan_pipeline
from .simulator import ChainSimulator, RoundTripResult
from .pipeline import (
    make_pipeline_mesh,
    make_pipeline_train_step,
    pipeline_forward,
    plan_on_devices,
    stack_for_pipeline,
)

__all__ = ["PipelinePlan", "plan_pipeline", "group_profile",
           "make_pipeline_mesh", "make_pipeline_train_step",
           "pipeline_forward", "plan_on_devices", "stack_for_pipeline",
           "ChainSimulator", "RoundTripResult"]

from repro.sharding.specs import (
    MeshPlan,
    make_plan,
    param_specs,
)

__all__ = ["MeshPlan", "make_plan", "param_specs"]

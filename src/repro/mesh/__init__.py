"""Mesh application layer: adaptive meshes + halo exchange + distributed
stencil on the partition core (the paper's primary workload)."""
from repro.mesh import amr, halo, plan_cache, simulate, stencil  # noqa: F401
from repro.mesh.amr import (  # noqa: F401
    AMRMesh,
    Transfer,
    apply_transfer,
    face_neighbors,
    feature_weights,
    refine_coarsen,
    stencil_coeffs,
    uniform_mesh,
)
from repro.mesh.halo import (  # noqa: F401
    HaloPlan,
    MovePlan,
    build_halo_plan,
    build_move_plan,
    owners_from_index,
    plan_quality_metrics,
)
from repro.mesh.plan_cache import PlanCache, PlanCacheStats  # noqa: F401
from repro.mesh.simulate import (  # noqa: F401
    SimConfig,
    build_trajectory,
    initial_field,
    run_distributed,
    run_reference,
)
from repro.mesh.stencil import reference_stencil, stencil_steps  # noqa: F401

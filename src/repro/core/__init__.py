"""The paper's contribution: hierarchization for the sparse grid combination
technique, as a composable JAX library.

Layer map (DESIGN.md Sect. 3):
  levels        — level-vector algebra, combination coefficients, flop
                  counts; downward-closed index sets (GeneralScheme)
  hierarchize   — layout strategies + (de)hierarchization entry points
  combination   — gather/scatter communication phase (subspace + embedded)
  executor      — PRODUCTION comm phase: bucket-batched hierarchization +
                  static index plan, one jitted ct_transform; incremental
                  plan rebuilds (extend_plan / update_plan_coefficients)
  adaptive      — dimension-adaptive refinement: surplus-scored index-set
                  growth driving incremental executor-plan extension
  engine        — the unified front door: ExecSpec (one execution config)
                  + CTEngine (multi-tenant continuous-batching serving
                  with signature-shared compiled executables)
  interpolation — nodal / hierarchical-basis evaluation (validation anchor)
  pde           — the black-box solvers of the compute phase
  iterated      — the iterated combination technique driver
  distributed   — shard_map comm phase (slab-sharded gathers) + grid-group placement
"""

from repro.core.hierarchize import dehierarchize, hierarchize  # noqa: F401
from repro.core.levels import (CombinationScheme, GeneralScheme,  # noqa: F401
                               combination_grids, downward_closure,
                               flops_eq1, flops_exact, grid_shape,
                               hierarchization_bytes, muls_reduced, num_points)

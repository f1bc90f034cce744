"""The paper's primary contribution: latency-optimal layer placement
(OULD / OULD-MP) over heterogeneous-bandwidth node topologies, plus the
system-model substrates (per-layer profiles, radio link model, RPG
mobility) and the heuristic baselines it is evaluated against.
"""

from .events import ChurnEvent, Event, EventKind, EventQueue, churn_events, poisson_process
from .heuristics import solve_heuristic
from .latency import Evaluation, evaluate
from .mobility import MultiGroupMobility, RPGMobility, RPGParams
from .ould import (IncrementalSolver, Problem, ResolveStats, Solution,
                   default_sparse_k, improvement_bound,
                   incremental_transfer_cost, placement_drift, solve_ould,
                   transfer_cost)
from .ould_mp import (MPResult, solve_offline_fixed, solve_ould_mp,
                      solve_static_resolve)
from .placement import (Stage, balanced_stages, ould_pipeline_stages,
                        stage_boundaries, to_stages)
from .planner import (HorizonView, IncrementalPlanner, NoisyHorizonView,
                      Plan, Planner, SnapshotView, StaleView, TopologyView,
                      available_planners, get_planner, make_view,
                      register_planner)
from .profiles import (LayerProfile, ModelProfile, lenet_profile, lm_profile,
                       vgg16_profile, vitb16_profile)
from .radio import RadioParams, TpuLinkModel, rate_matrix, sinr_matrix

__all__ = [
    "ChurnEvent", "Evaluation", "Event", "EventKind", "EventQueue",
    "HorizonView", "IncrementalPlanner", "IncrementalSolver", "LayerProfile",
    "MPResult", "ModelProfile", "MultiGroupMobility", "NoisyHorizonView",
    "Plan", "Planner",
    "Problem", "RPGMobility", "RPGParams", "RadioParams", "ResolveStats",
    "SnapshotView", "Solution", "Stage", "StaleView", "TopologyView",
    "TpuLinkModel",
    "available_planners", "balanced_stages", "churn_events",
    "default_sparse_k", "evaluate",
    "get_planner", "improvement_bound", "incremental_transfer_cost",
    "lenet_profile",
    "lm_profile", "make_view", "ould_pipeline_stages", "placement_drift",
    "poisson_process",
    "rate_matrix", "register_planner", "sinr_matrix", "solve_heuristic",
    "solve_offline_fixed", "solve_ould", "solve_ould_mp",
    "solve_static_resolve", "stage_boundaries", "to_stages", "transfer_cost",
    "vgg16_profile", "vitb16_profile",
]

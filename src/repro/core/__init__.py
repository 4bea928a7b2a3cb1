"""Cocktail core: cost-efficient, data-skew-aware online data scheduling.

Public API:
  CocktailConfig, ShapeConfig, SliceParams, split_config, stack_slice_params,
  NetworkState, QueueState, Multipliers, Decision,
  SchedulerState, init_state           -- state types (batch-first split)
  sample_network_state, framework_cost -- stochastic environment (Sec. II)
  step, run, AlgoSpec and the named specs (DS, LDS, NO_SDC, ...) -- Sec. III
  COLLECTION_POLICIES, TRAINING_POLICIES, PolicyTable, SWITCHED, with_policy
                                       -- indexed policy tables; branch-free
                                          (lax.switch) per-slice dispatch
  SliceJob, FleetEngine.from_jobs      -- K-slice vmapped fleet scheduling:
                                          homogeneous, ragged mixed-shape
                                          (padding + entity masks) and
                                          mixed-policy fleets in ONE program
  PackedState, PackedRecord            -- what FleetEngine.run returns: the
                                          state and records, one buffer
                                          each, read like the trees
  metrics                              -- Sec. IV evaluation metrics
"""
from .datasche import (ALL_SPECS, COLLECTION_POLICIES, CU_FULL, DS, DS_EXACT,
                       EC_FULL, EC_SELF, GREEDY, LDS, NO_LSA, NO_SDC, NO_SLT,
                       SWITCHED, SWITCHED_NOAID, TRAINING_POLICIES, AlgoSpec,
                       PolicyTable, SlotRecord, collection_weights, run,
                       skew_degree, stack_slot_records, step, training_weights,
                       with_policy)
from .fleet import (FleetEngine, PackedRecord, PackedState, ragged_pad_shape,
                    trim_state)
from .job import SliceJob, as_jobs
from .network import framework_cost, sample_network_state
from .types import (MASKED_WEIGHT, CocktailConfig, Decision, Multipliers,
                    NetworkState, QueueState, SchedulerState, ShapeConfig,
                    SliceParams, entity_masks, init_state, mask_pairs,
                    split_config, stack_slice_params)

__all__ = [
    "ALL_SPECS", "AlgoSpec", "CocktailConfig", "COLLECTION_POLICIES",
    "CU_FULL", "DS", "DS_EXACT", "Decision", "EC_FULL", "EC_SELF",
    "FleetEngine", "GREEDY", "LDS", "Multipliers", "NetworkState", "NO_LSA",
    "NO_SDC", "NO_SLT", "PackedRecord", "PackedState", "PolicyTable",
    "QueueState", "SWITCHED",
    "SWITCHED_NOAID",
    "SchedulerState", "ShapeConfig", "SliceJob", "SliceParams", "SlotRecord",
    "TRAINING_POLICIES", "MASKED_WEIGHT", "as_jobs", "collection_weights",
    "entity_masks", "framework_cost", "init_state", "mask_pairs",
    "ragged_pad_shape", "run", "sample_network_state", "skew_degree",
    "split_config", "stack_slice_params", "stack_slot_records", "step",
    "training_weights", "trim_state", "with_policy",
]

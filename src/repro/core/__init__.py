"""Core: the paper's doubly-pipelined dual-root reduction-to-all + siblings."""

from repro.core.autotune import (AutotuneCache, TuneResult, candidate_settings,
                                 tune)
from repro.core.collectives import (CollectiveConfig, Plan, all_reduce,
                                    bucket_sizes, bucketed_all_reduce, plan,
                                    structured_all_reduce)
from repro.core.cost_model import (ALGORITHMS, COMPRESS_FACTOR, PAPER_HYDRA,
                                   TPU_V5E, TPU_V5E_INTERPOD, CommModel,
                                   best_algorithm, dptree_time, hier_time,
                                   optimal_blocks, redbcast_time, ring_time,
                                   sptree_time)
from repro.core.dptree import (dptree_allreduce, hier_allreduce,
                               redbcast_allreduce, ring_allreduce,
                               sptree_allreduce)
from repro.core.simulator import simulate_allreduce
from repro.core.topology import (HierarchicalTopology, TreeTopology,
                                 as_levels, build_dual_tree, build_hierarchy,
                                 build_single_tree, expand_tree_over_stripes,
                                 resolve_levels, validate_topology)

"""Core: exact top-K inference for SEP-LR models (the paper's contribution).

Public API:
  SepLRModel, build_index, TopKIndex
  naive_topk                      — baseline (matmul + exact top-K)
  threshold_topk / *_np           — the Threshold Algorithm (Alg. 2)
  fagin_topk_np                   — Fagin's Algorithm (Alg. 1)
  partial_threshold_topk_np       — Partial TA (Alg. 3)
  blocked_topk (+batched)         — TPU-native Block Threshold Algorithm
  norm_pruned_topk                — Cauchy-Schwarz norm screening (beyond paper)
  sharded_naive_topk / sharded_blocked_topk / hierarchical_merge_topk

Engine layer (DESIGN.md):
  pruned_block_scan, ScanStrategy — the unified driver every engine runs on
  ta_round_strategy / blocked_lists_strategy / norm_block_strategy
  Engine, EngineContext, register_engine, get_engine, list_engines,
  engine_names, select_engine     — the name-keyed engine registry
  SegmentedCatalogue              — streaming (base + delta + tombstone)
                                    exact top-K over a mutating catalogue
"""

from repro.core.blocked import (
    blocked_topk,
    blocked_topk_batched,
    chunked_ta_topk,
    chunked_ta_topk_batched,
    norm_pruned_topk,
)
from repro.core.driver import (
    ScanState,
    ScanStrategy,
    merge_topk_sorted,
    pruned_block_scan,
)
from repro.core.engines import (
    CostTable,
    Engine,
    EngineContext,
    batch_bucket,
    engine_names,
    get_engine,
    list_engines,
    m_bucket,
    register_engine,
    select_engine,
    trace_totals,
)
from repro.core.fagin import FaginStats, fagin_topk_np
from repro.core.index import TopKIndex, build_index
from repro.core.layout import (
    DEFAULT_PREFIX_DEPTH,
    ListMajorLayout,
    NormMajorLayout,
    RowMajorLayout,
    ShardedNormLayout,
    build_layout,
    layout_names,
)
from repro.core import faults
from repro.core.lsm import (DEFAULT_L1_CAPACITY_FACTOR,
                            ShardedLsmCatalogue)
from repro.core.naive import (TopKResult, certificate_gaps,
                              certified_counts, naive_topk)
from repro.core.segments import (
    DEFAULT_DELTA_CAPACITY,
    DeltaSegment,
    QueryInfo,
    SegmentStats,
    SegmentedCatalogue,
    Snapshot,
    delta_bucket,
)
from repro.core.partial import PartialTAStats, partial_threshold_topk_np
from repro.core.seplr import (
    SepLRModel,
    from_cosine_similarity,
    from_linear_multilabel,
    from_matrix_factorization,
    from_pairwise_kronecker,
    kronecker_query,
    normalize_query,
    random_model,
)
from repro.core.sharded import (
    hierarchical_merge_topk,
    sharded_blocked_topk,
    sharded_naive_topk,
    sharded_norm_topk,
)
from repro.core.strategies import (
    blocked_lists_strategy,
    list_prefix_strategy,
    norm_block_strategy,
    rank_gather_first_keys,
    ta_round_strategy,
)
from repro.core.threshold import (
    TAStats,
    threshold_topk,
    threshold_topk_from_index,
    threshold_topk_np,
)

__all__ = [
    "SepLRModel", "TopKIndex", "TopKResult", "TAStats", "FaginStats",
    "PartialTAStats", "build_index", "naive_topk", "threshold_topk",
    "threshold_topk_from_index", "threshold_topk_np", "fagin_topk_np",
    "partial_threshold_topk_np", "blocked_topk", "blocked_topk_batched",
    "chunked_ta_topk", "chunked_ta_topk_batched",
    "norm_pruned_topk", "sharded_naive_topk", "sharded_blocked_topk",
    "hierarchical_merge_topk", "from_cosine_similarity",
    "from_matrix_factorization", "from_linear_multilabel",
    "from_pairwise_kronecker", "kronecker_query", "normalize_query",
    "random_model",
    "sharded_norm_topk",
    # engine layer
    "ScanState", "ScanStrategy", "pruned_block_scan", "merge_topk_sorted",
    "ta_round_strategy", "blocked_lists_strategy", "list_prefix_strategy",
    "rank_gather_first_keys", "norm_block_strategy",
    "Engine", "EngineContext", "register_engine", "get_engine",
    "CostTable",
    "list_engines", "engine_names", "select_engine", "batch_bucket",
    # layout subsystem
    "RowMajorLayout", "NormMajorLayout", "ListMajorLayout",
    "ShardedNormLayout", "build_layout", "layout_names",
    "DEFAULT_PREFIX_DEPTH",
    # streaming catalogue subsystem
    "SegmentedCatalogue", "Snapshot", "DeltaSegment", "QueryInfo",
    "SegmentStats", "delta_bucket", "DEFAULT_DELTA_CAPACITY",
    # LSM ladder (DESIGN.md §15)
    "ShardedLsmCatalogue", "DEFAULT_L1_CAPACITY_FACTOR",
    # robustness layer (DESIGN.md §12)
    "certificate_gaps", "certified_counts", "faults",
]

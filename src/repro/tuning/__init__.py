"""Persistent block-shape autotuning (paper Sec. 5.1, Fig. 14).

Layers:

* ``costmodel`` — structural candidate enumeration (rank-generic) + the
  measurement protocol;
* ``cache``     — persistent per-platform JSON store
  (``$REPRO_TUNE_CACHE`` or ``~/.cache/repro-tune/``), schema-versioned;
* ``session``   — structural-rank → measure-top-k → record, with a
  cache-hit fast path; the ``block="auto"`` resolvers live here. The
  fused-engine resolver (``auto_block_nd``) keys the cache on the
  serialized ``StencilPlan`` identity, so rank-1/2/3 problems share one
  persistent cache with distinct, stable keys;
* ``cli``       — ``python -m repro.tuning warm|show|clear``.
"""
from repro.tuning.cache import (  # noqa: F401
    SCHEMA_VERSION,
    TuningCache,
    TuningKey,
    TuningRecord,
    candidate_label,
    current_backend,
    default_cache_dir,
    format_block,
)
from repro.tuning.costmodel import (  # noqa: F401
    Candidate,
    Candidate1D,
    LANE,
    SUBLANE,
    VMEM_BUDGET,
    autotune,
    axis_tile_options,
    domain_axis_options,
    enumerate_candidates,
    enumerate_candidates_1d,
    enumerate_candidates_nd,
    enumerate_cross_strategy_nd,
    halo_overhead,
    hwc_candidate,
    time_candidate,
    vmem_working_set,
)
from repro.tuning.session import (  # noqa: F401
    TuningSession,
    auto_block_3d,
    auto_block_nd,
    auto_block_xcorr1d,
    auto_fuse_nd,
    auto_strategy_nd,
    default_session,
    fused3d_candidates,
    fused3d_key,
    fused_nd_candidates,
    fused_nd_key,
    lookup_fused3d,
    lookup_fused_nd,
)

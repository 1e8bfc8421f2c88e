"""The batched (ensemble) kernels' share of their HBM roofline.

The same reduction as ``kernel_roofline``, read in the serving cells,
which report ``member_updates_per_s`` instead of ``point_updates_per_s``.
"""
from __future__ import annotations

from bench.metrics.kernel_roofline import read  # noqa: F401

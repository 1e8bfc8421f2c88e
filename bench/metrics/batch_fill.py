"""Share of the serving batches' slots that held a member.

Members served, summed over ``BatchReport.batch`` of the batches run in
the window, over (batches * ``max_batch``). A count: it repeats exactly
for a given traffic mix and batching policy.
"""
from __future__ import annotations


def read(run) -> float | None:
    if not run.batches or not run.max_batch:
        return None
    return 100.0 * sum(run.batches) / (len(run.batches) * run.max_batch)

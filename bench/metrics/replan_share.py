"""Share of the window's batches that planned afresh (``JobResult.reused`` false), in %.

A count made by the program (``core/schedule_cache``): a batch that replays
the cached plan skips the statistics pull and the host scheduler.
"""


def read(run):
    return 100.0 * sum(1 for b in run.batches if not b["reused"]) / run.num_batches

"""The combiner's share of its roofline, in %.

A combiner has to read each valid map pair, its 4-byte key and its
values, at least once; that is the HBM floor of ``bench/floors.py``
(``hbm_s``: the batch's valid pairs at the chip's HBM rate, with its
reducers' outputs, a few KiB). The floors summed over the window's
batches, over the combiner's device time in the window (``jit_combine``,
``combine_ms.device_s``). ``None`` when the program runs no combiner.
"""

import layout

combine_ms = layout.metric_module("combine_ms")


def read(run):
    s = combine_ms.device_s(run)
    return None if not s else 100.0 * sum(f["hbm_s"] for f in run.floors) / s

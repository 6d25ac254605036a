"""The device's idle share of the traced window, in %.

1 - (union of the intervals in which an ``XLA Ops`` event runs) / (the
harness's ``bench.window`` span), averaged over the chips the cell uses.
"""


def read(run):
    return 100.0 * (1.0 - run.busy_s / run.window_s)

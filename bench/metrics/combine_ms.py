"""The combiner: device time of its executable per batch, in ms.

Reads the ``XLA Modules`` events named ``jit_combine`` (the engine's
``jax.jit`` of ``combine`` in ``MapReduceJob._run_combine``, dispatched
when the configuration sets ``combine``), summed over the window on each
chip, divided by the window's batches, averaged over chips. ``None`` when
the program runs no combiner.
"""

from trace_reduce import TraceError

PATTERN = r"^jit_combine\b"


def device_s(run):
    """The combiner's device time in the window, averaged over chips, in s;
    ``None`` without a ``jit_combine`` executable."""
    try:
        per_chip = run.trace_module_ns(PATTERN)
    except TraceError:
        return None
    return sum(per_chip) / len(per_chip) * 1e-9


def read(run):
    s = device_s(run)
    return None if s is None else s / run.num_batches * 1e3

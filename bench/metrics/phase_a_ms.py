"""Phase A (map + statistics): device time of its executable per batch, in ms.

Reads the ``XLA Modules`` events named ``jit_phase_a`` (the engine's
``jax.jit`` of ``phase_a`` in ``MapReduceJob.run``), summed over the
window on each chip, divided by the window's batches, averaged over chips.
"""

PATTERN = r"^jit_phase_a\b"


def read(run):
    per_chip = run.trace_module_ns(PATTERN)
    return sum(per_chip) / len(per_chip) / run.num_batches * 1e-6

"""Phase B (spill, copy, reduce): device time of its executable per batch, in ms.

Reads the ``XLA Modules`` events named ``jit_phase_b`` (the engine's
``jax.jit`` of ``phase_b`` in ``MapReduceJob._execute``), summed over the
window on each chip, divided by the window's batches, averaged over chips.
A batch whose reused plan overflowed runs phase B twice; both count.
"""

PATTERN = r"^jit_phase_b\b"


def read(run):
    per_chip = run.trace_module_ns(PATTERN)
    return sum(per_chip) / len(per_chip) / run.num_batches * 1e-6

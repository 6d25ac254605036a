"""Phase B's share of its roofline, in %.

The least time phase B could take (``bench/floors.py``: the larger of the
HBM floor and, on more than one chip, the ICI floor), summed over the
window's batches, over phase B's device time in the window (``jit_phase_b``
executables, averaged over chips). ``run.floor_bound`` says which floor
bounds it.
"""

PATTERN = r"^jit_phase_b\b"


def read(run):
    per_chip = run.trace_module_ns(PATTERN)
    device_s = sum(per_chip) / len(per_chip) * 1e-9
    return 100.0 * sum(f["floor_s"] for f in run.floors) / device_s

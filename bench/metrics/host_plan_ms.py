"""The host planner's time per batch, in ms: ``_plan`` with OS4M's assignment,
the waves and the capacities.

Summed duration of the engine's ``os4m.plan`` host spans in the window,
divided by the window's batches (``bench/program_spans.py``). A batch that
reuses its plan has none. ``None`` when the program records no spans.
"""

import program_spans


def read(run):
    spans = program_spans.read(run)
    return None if spans is None else spans.host_ms(program_spans.PLAN)

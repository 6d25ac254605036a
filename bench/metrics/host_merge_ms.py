"""The host merge's time per batch, in ms: slot outputs summed, wire
accounting, speed feedback and the ``JobResult``.

Summed duration of the engine's ``os4m.merge`` host spans in the window,
divided by the window's batches (``bench/program_spans.py``). ``None``
when the program records no spans.
"""

import program_spans


def read(run):
    spans = program_spans.read(run)
    return None if spans is None else spans.host_ms(program_spans.MERGE)

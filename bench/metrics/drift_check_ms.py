"""Device idle time under the schedule cache's drift decision per batch, in ms.

The part of the engine's ``os4m.decide`` host spans during which the chip
runs no op, averaged over chips, divided by the window's batches
(``bench/program_spans.py``). The host's wait for phase A inside the span
is not idle. ``None`` when the program records no spans.
"""

import program_spans


def read(run):
    spans = program_spans.read(run)
    return None if spans is None else spans.idle_ms(program_spans.DECIDE)

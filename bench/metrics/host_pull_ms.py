"""Device idle time under the device-to-host pulls per batch, in ms.

The part of the union of the engine's ``os4m.stats_pull`` (statistics) and
``os4m.output_pull`` (overflow count, outputs, wire counters) host spans
during which the chip runs no op, averaged over chips, divided by the
window's batches (``bench/program_spans.py``). The wait for phase B to end
inside a pull is not idle. ``None`` when the program records no spans.
"""

import program_spans


def read(run):
    spans = program_spans.read(run)
    if spans is None:
        return None
    return spans.idle_ms(program_spans.STATS_PULL, program_spans.OUTPUT_PULL)

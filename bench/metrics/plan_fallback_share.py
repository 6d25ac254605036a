"""Share of the window's fresh plans in which OS4M's assignment ran the BSS DP, in %.

The engine's ``"os4m"`` scheduler takes LPT's schedule where a lower bound
certifies it within ``eta`` of the optimum, and runs the BSS DP only where
none does, inside an ``os4m.plan.bss`` host span. This is the number of
those spans over the number of ``os4m.plan`` spans in the window
(``bench/program_spans.py``).

A trace cannot tell a span that never opened from one the program does not
have, so the program's own list of span names, ``repro.core.spans`` as the
run loaded it, decides: an engine that names no ``os4m.plan.bss`` (one whose
``"os4m"`` runs the DP on every plan) gives ``None``, as do a program that
records no spans and a window in which no batch planned.
"""

import sys

import program_spans

PLAN_BSS = "os4m.plan.bss"


def read(run):
    names = sys.modules.get("repro.core.spans")
    if names is None or PLAN_BSS not in vars(names).values():
        return None
    spans = program_spans.read(run)
    if spans is None:
        return None
    plans = len(spans.named(program_spans.PLAN))
    if not plans:
        return None
    return 100.0 * len(spans.named(PLAN_BSS)) / plans

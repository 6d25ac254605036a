"""Names of the spans and scopes the engine records for a profiler trace.

Host spans are ``jax.profiler.TraceAnnotation`` opened by
:meth:`repro.core.mapreduce.MapReduceJob.run` on the thread that calls it.
They are recorded only while a profiler session runs, on the trace's own
clock, so a device op and the host work around it can be compared; with
no session each costs about a microsecond. Their keyword arguments become
stats of the trace event (the counters below). One batch is one tree:

    os4m.batch (batch)                  the whole of ``run()``
      os4m.decide                       drift check and cost gate
        os4m.stats_pull (bytes)         only when the cost gate pulls K^(i)
      os4m.stats_pull (bytes)           K^(i), its column sum or the prefix sketch
      os4m.plan (valid_pairs, input_pairs)
        os4m.plan.assign                the scheduler (hash / sketch / BSS / auto)
      os4m.phase_b                      each dispatch of a phase-B executor
        os4m.jit_build (key)            first call of a newly built executable
      os4m.output_pull (bytes)          overflow count, outputs and counts, wire vector
      os4m.merge                        slot merge, wire accounting, speed feedback

``batch`` is the job's running batch index; ``bytes`` the bytes pulled to
the host; ``valid_pairs`` the pairs the statistics count (sum of the key
distribution) and ``input_pairs`` the slots' map outputs (m x k), so their
ratio is the share of the spilled pairs that are real; ``key`` the first
element of the jit-cache key (``"a"`` phase A, ``"b"`` phase B, ...).
``os4m.jit_build`` also opens inside ``os4m.batch`` for phase A.

Readers: ``bench/program_spans.py`` intersects the host spans with the
device's idle gaps for the per-layer metrics ``host_plan_ms``
(``os4m.plan``), ``drift_check_ms`` (``os4m.decide``), ``host_pull_ms``
(``os4m.stats_pull`` and ``os4m.output_pull``) and ``host_merge_ms``
(``os4m.merge``); ``bench/trace_reduce.breakdown`` labels each idle gap by
its two innermost spans; ``bench/tools/span_report.py`` prints every span,
its counters and the device scopes per batch.

Device scopes are ``jax.named_scope`` in the phase-B helpers, so every
phase-B variant carries them. They change op metadata only (the HLO
``op_name`` and a device trace's ``tf_op`` stat), not fusion or run time.
Read by ``bench/tools/span_report.py``.
"""

BATCH = "os4m.batch"
DECIDE = "os4m.decide"
STATS_PULL = "os4m.stats_pull"
PLAN = "os4m.plan"
PLAN_ASSIGN = "os4m.plan.assign"
PHASE_B = "os4m.phase_b"
OUTPUT_PULL = "os4m.output_pull"
MERGE = "os4m.merge"
JIT_BUILD = "os4m.jit_build"

SPILL = "os4m.spill"    # rank each pair in its (chunk, slot) group and bucket it
COPY = "os4m.copy"      # the all-to-all of a chunk (and the coded path's exchanges)
REDUCE = "os4m.reduce"  # the segment reduce of a chunk, or of the whole input

DEVICE_SCOPES = (SPILL, COPY, REDUCE)

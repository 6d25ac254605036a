"""Names of the spans and scopes the engine records for a profiler trace.

Host spans are ``jax.profiler.TraceAnnotation`` opened by
:meth:`repro.core.mapreduce.MapReduceJob.run` on the thread that calls it.
They are recorded only while a profiler session runs, on the trace's own
clock, so a device op and the host work around it can be compared; with
no session each costs about a microsecond. Their keyword arguments become
stats of the trace event (the counters below). One batch is one tree:

    os4m.batch (batch)                  the whole of ``run()``
      os4m.combine (capacity, combined_pairs)
                                        the combiner's dispatch and any re-run at K
        os4m.stats_pull (bytes)         per-shard combined pair counts
      os4m.decide                       drift check and cost gate
        os4m.stats_pull (bytes)         only when the cost gate pulls K^(i)
      os4m.stats_pull (bytes)           K^(i), its column sum or the prefix sketch
      os4m.plan (valid_pairs, input_pairs)
        os4m.plan.assign                the scheduler (hash / sketch / OS4M / auto)
          os4m.plan.bss                 OS4M's BSS DP, where LPT is not certified
      os4m.phase_b (bucket_rows)        each dispatch of a phase-B executor
        os4m.jit_build (key)            first call of a newly built executable
      os4m.output_pull (bytes)          overflow count, outputs and counts, wire vector
      os4m.merge                        slot merge, wire accounting, speed feedback

``batch`` is the job's running batch index; ``bytes`` the bytes pulled to
the host; ``valid_pairs`` the pairs the statistics count (sum of the key
distribution) and ``input_pairs`` the slots' map outputs (m x k), so their
ratio is the share of the spilled pairs that are real; ``bucket_rows``
the rows one shard's spill writes under the plan phase B runs (m x the
sum of the chunk capacities; m x the capacity when not pipelined), so
its ratio to ``input_pairs`` / m is the share of a shard's pairs the
spill touches past its sort; ``key`` the first element of the jit-cache
key (``"a"`` phase A, ``"b"`` phase B, ...).
``os4m.jit_build`` also opens inside ``os4m.batch`` for phase A and inside
``os4m.combine`` for the combiner. ``os4m.combine`` opens only under
``MapReduceConfig(combine=True)``: ``capacity`` is the static per-shard
capacity C its output was compacted to, ``combined_pairs`` the combined
pairs of all shards, the loads that the statistics then count.
``os4m.plan.bss`` is opened by :func:`repro.core.scheduler.schedule_os4m`,
only when LPT's makespan is not within ``1 + eta`` of its lower bound and
the BSS DP runs.

Readers: ``bench/program_spans.py`` intersects the host spans with the
device's idle gaps for the per-layer metrics ``host_plan_ms``
(``os4m.plan``), ``drift_check_ms`` (``os4m.decide``), ``host_pull_ms``
(``os4m.stats_pull`` and ``os4m.output_pull``) and ``host_merge_ms``
(``os4m.merge``); ``bench/metrics/plan_fallback_share.py`` counts the
``os4m.plan.bss`` spans per ``os4m.plan`` span;
``bench/trace_reduce.breakdown`` labels each idle gap by its two
innermost spans; ``bench/tools/span_report.py`` prints every span, its
counters and the device scopes per batch.

Device scopes are ``jax.named_scope`` in the phase-B helpers, so every
phase-B variant carries them, and in the combiner (``os4m.combine``, the
``jit_combine`` executable). They change op metadata only (the HLO
``op_name`` and a device trace's ``tf_op`` stat), not fusion or run time.
``bench/tools/span_report.py`` reads the three phase-B scopes; the
combiner's device time is read from its executable (``combine_ms``).
"""

BATCH = "os4m.batch"
DECIDE = "os4m.decide"
STATS_PULL = "os4m.stats_pull"
PLAN = "os4m.plan"
PLAN_ASSIGN = "os4m.plan.assign"
PLAN_BSS = "os4m.plan.bss"
PHASE_B = "os4m.phase_b"
OUTPUT_PULL = "os4m.output_pull"
MERGE = "os4m.merge"
COMBINE = "os4m.combine"  # also the device scope of the combiner's reduce by key
JIT_BUILD = "os4m.jit_build"

SPILL = "os4m.spill"    # sort pairs by (chunk, slot) group; gather each bucket row
COPY = "os4m.copy"      # the all-to-all of a chunk
REDUCE = "os4m.reduce"  # the segment reduce of a chunk, or of the whole input

DEVICE_SCOPES = (SPILL, COPY, REDUCE, COMBINE)

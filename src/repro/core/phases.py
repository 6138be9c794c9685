"""Stable names of a federated round's phases, as a profiler trace shows
them.

Device work is named with ``jax.named_scope``: the name lands in the
``op_name`` metadata of every HLO op traced under it (after any transform
wrappers: ``round.phase2/vmap(transpose(jvp()))/conv_general_dilated``),
so a trace reader can put each device op down to the phase that issued
it. Scopes change only metadata, never the compiled program's work. Where
scopes nest, the innermost one names the op: a ``round.sample`` inside a
streaming phase scope counts as the sampler.

Host work of ``RoundEngine.run`` is named with
``jax.profiler.TraceAnnotation`` spans, which the profiler writes on the
same clock as the device trace; they cost a few microseconds a segment.
"""
from __future__ import annotations

# -- device scopes (jax.named_scope), inside the jitted round ----------------
SAMPLE = "round.sample"                # cohort gather and the two views
PHASE1 = "round.phase1"                # client encodings, Eq.-3 statistics
                                       # and their aggregation (or channel)
PHASE2 = "round.phase2"                # per-client forward, backward, local
                                       # steps and deltas
DELTA_AVERAGE = "round.delta_average"  # weighted average of the deltas
SERVER_UPDATE = "round.server_update"  # the server optimizer's step

# -- host spans (jax.profiler.TraceAnnotation), in RoundEngine.run -----------
CARRY_COPY = "engine.carry_copy"       # copy of the caller's carry
DISPATCH = "engine.dispatch"           # enqueueing one segment program
COLLECT = "engine.collect"             # per-segment metric columns, concat
CHECKPOINT = "engine.checkpoint"       # writing a checkpoint

"""100 x device seconds of the ops under the given scopes / device busy
seconds, of a SERVING cell's traced segment: `trace_scope_share`'s reading
(args `scopes`, `phase`, `categories` as there; None where the trace holds no
op under a scope of the program's table: a parent, a CPU rehearsal), under a
name of its own because `tests/test_scope_readers.py` holds every metric file
of a `trace_scope_*` reader to the recorded TRAINING traces, where a scope that
only a served layer opens (a window layer's ring) has no op to read."""

from . import trace_scope_share


def read(evidence, args):
    return trace_scope_share.read(evidence, args)

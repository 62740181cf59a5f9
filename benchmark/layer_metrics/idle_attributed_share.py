"""Share of the first device's idle seconds in the traced window that lie
under host work of the engine thread: each gap between device operations
goes to the innermost ``serving/*`` annotation over its middle;
``serving/admit``, ``/prefill``, ``/decode_dispatch``, ``/emit`` and the
self time of ``serving/iteration`` are host work, ``serving/readback`` and
``serving/idle`` are the host waiting (the cause is then on the device, in
the runtime, or in the traffic).  Prints the whole table and the longest
gaps: idle that comes as one long gap is an event, not a cadence.
"""
LAYER = "scheduler"
SOURCE = "device_trace"
MOVES = "serve_itl_p95_ms"
DEVICE = True   # True: only a chip run can give it


def read(obs):
    from harness import program_spans as ps, result
    seen = ps.observed(obs)
    if seen is None:
        return None
    table = ps.idle_by_span(*seen)
    result.say("idle_by_engine_span", **dict(
        sorted(table.items(), key=lambda kv: -kv[1])))
    result.say("idle_longest_gaps", gaps=len(seen[0]), longest=[
        [1e3 * sec, name] for sec, name in sorted(ps.named_gaps(*seen))[:-6:-1]])
    return ps.attributed_share(table)

"""Of the prefill chunk programs sent in the window, the share that rode a
decode step as one joint program (``stats()``: ``chunks_joint`` over
``chunks_joint + chunks_alone``, as differences across the window).  A pool
whose model offers the joint pass (each layer's feed-forward once over the
decode rows and the chunk's rows) carries a chunk in the pass's decode step
whenever a slot decodes; a chunk beside an idle pool, or one whose rows are
read before the step (a prefix cache's extract), goes out alone.  In a
saturated cell nearly every chunk rides.  Bucketed prefills (a prompt no
longer than a chunk) are neither.  None where the program has no such
counters, or the window sent no chunk.
"""
LAYER = "programs"
SOURCE = "program_counter"
MOVES = "serve_itl_p95_ms"
DEVICE = False   # True: only a chip run can give it


def read(obs):
    from harness import program_spans as ps
    joint = ps.delta(obs, "chunks_joint")
    alone = ps.delta(obs, "chunks_alone")
    if joint is None or alone is None:
        return None
    return ps.ratio(joint, joint + alone, 100.0)

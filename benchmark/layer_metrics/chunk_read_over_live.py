"""Cache positions the prefill chunks' attention read over those the chunks
had live (``chunk_positions_read`` over ``chunk_positions_live``, the
scheduler's counters as differences across the window, a full layer).  A
chunk that starts at position ``s`` with ``w`` tokens has ``s + w`` places
live in its slot's row; one that reads live key blocks only reads that
rounded up to the block and stands a little above 1; one that reads its
slot's whole row reads ``max_len`` wherever it stands.
"""
LAYER = "kernels"
SOURCE = "program_counter"
MOVES = "serve_itl_p95_ms"
DEVICE = False   # True: only a chip run can give it


def read(obs):
    from harness import program_spans as ps
    return ps.ratio(ps.delta(obs, "chunk_positions_read"),
                    ps.delta(obs, "chunk_positions_live"))

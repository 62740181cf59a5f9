"""What a chunk's place in its row costs a pass, end to end: among the
passes that carried exactly one prefill chunk of the full width (the
window's pass records, ``stats()["pass_log"]``; the slow passes left
out), the median step gap of the third whose chunk starts latest in its
row less that of the third whose chunk starts earliest.  Near zero where a
chunk reads its slot's row whole, whatever is live in it; a few hundredths
below zero is the same reading.  None where the program keeps no pass log,
or under 30 such passes a third.
"""
LAYER = "programs"
SOURCE = "program_span"
MOVES = "serve_itl_p95_ms"
DEVICE = False   # True: only a chip run can give it


def read(obs):
    from harness import pass_log as pl
    return pl.read(obs, lambda rec: pl.chunk_position_cost_ms(
        rec, obs["cfg"]["serving"]["prefill_chunk"]))

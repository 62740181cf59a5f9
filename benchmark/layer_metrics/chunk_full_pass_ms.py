"""Mean step gap of the passes that carried exactly one prefill chunk of
the full width (the configuration's ``prefill_chunk``), from the window's
pass records (``stats()["pass_log"]``): the pass the tail sits on, without
the cheaper widths of a prompt's remainder that ``engine_iter_prefill_ms``
averages in, and without the window's slow passes (over three medians of
their group: ``stall_share`` counts those seconds).  None where the program
keeps no pass log, or under ten such passes.
"""
LAYER = "programs"
SOURCE = "program_span"
MOVES = "serve_itl_p95_ms"
DEVICE = False   # True: only a chip run can give it


def read(obs):
    from harness import pass_log as pl
    return pl.read(obs, lambda rec: pl.chunk_full_pass_ms(
        rec, obs["cfg"]["serving"]["prefill_chunk"]))

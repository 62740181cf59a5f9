"""What of the prefill scans' positions was padding: 1 - real positions
over positions scanned (``stats()``: ``ssm_scan_positions_real``,
``ssm_scan_positions``, as differences across the window, both summed over
the state layers).  A prompt's last chunk is padded to a power-of-two
width at its end (a state cannot be handed a position twice, so the chunk
is not moved back over the prompt as a row cache's is), and a bucketed
prompt to its bucket: the scan runs over the padding and the state does
not move.
"""
LAYER = "programs"
SOURCE = "program_counter"
MOVES = "serve_itl_p95_ms"
DEVICE = False   # True: only a chip run can give it


def read(obs):
    from harness import program_spans as ps
    share = ps.ratio(ps.delta(obs, "ssm_scan_positions_real"),
                     ps.delta(obs, "ssm_scan_positions"))
    return None if share is None else 100.0 * (1.0 - share)

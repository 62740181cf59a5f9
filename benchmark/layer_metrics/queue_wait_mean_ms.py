"""Mean wait of a request for a slot, enqueue to slot assignment, over the
requests admitted in the window (``queue_wait_seconds`` over ``admitted``,
the scheduler's counters as differences): the part of the time to the
first token that is queueing and not prefill.
"""
LAYER = "scheduler"
SOURCE = "program_span"
MOVES = "serve_tokens_per_s"
DEVICE = False   # True: only a chip run can give it


def read(obs):
    from harness import program_spans as ps
    return ps.ratio(ps.delta(obs, "queue_wait_seconds"),
                    ps.delta(obs, "admitted"), 1e3)

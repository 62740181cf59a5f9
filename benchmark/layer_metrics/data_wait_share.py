"""Share of the window that the dispatch loop waited for data
(``data_wait_s`` of ``Optimizer.window_records``). Near 0 here: the
batch is device-resident, which is the guard that these cells measure
the step and not the feeder.
"""
LAYER = "host input pipeline"
SOURCE = "program_span"
MOVES = "train_mfu"
DEVICE = False   # True: only a chip run can give it


def read(obs):
    r = obs.get("records")
    if not r:
        return None
    return 100.0 * r["data_wait_s"] / r["seconds"]

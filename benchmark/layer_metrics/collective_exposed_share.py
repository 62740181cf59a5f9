"""Share of the traced window in which a device sat in all-gather / all-
reduce / reduce-scatter with no other operation running, averaged over
the devices.
"""
LAYER = "collectives"
SOURCE = "device_trace"
MOVES = "train_mfu"
DEVICE = True   # True: only a chip run can give it


def read(obs):
    t = obs.get("trace")
    if t is None or obs.get("chips", 1) < 2 or not t.devices():
        return None
    return 100.0 * t.collective_exposed_s() / t.window_s

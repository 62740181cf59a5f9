"""Time the conv+BN layers' bytes need at the HBM peak, over the device
time of the trace's convolution fusions.  The bytes are those no
implementation can avoid (``flops.resnet_conv_bn_bytes_per_step``: three
passes over every map), so the share cannot pass 100%.  On this compiler
a convolution with its fused neighbours is a ``kOutput`` fusion (named
``convolution_*`` or plain ``fusion``); in a ResNet step the classifier's
matmul is the only other one, under 1% of their time.  HBM-bound.
"""
LAYER = "kernels"
SOURCE = "device_trace"
MOVES = "train_mfu"
DEVICE = True   # True: only a chip run can give it


def read(obs):
    t = obs.get("trace")
    if t is None or obs.get("kind") != "resnet" or not t.devices():
        return None
    from harness import flops
    secs = t.ops_seconds(
        lambda cat, _t: cat in ("convolution_fusion", "output_fusion"))
    if secs <= 0:
        return None
    nbytes = flops.resnet_conv_bn_bytes_per_step(obs["cfg"], obs["job"]["batch"])
    least = nbytes * obs["trace_steps"] / obs["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / secs

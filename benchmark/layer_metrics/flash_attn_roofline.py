"""Least time of one step's causal attention on one chip (the larger of
operations over the bf16 peak and bytes over the HBM peak; at T=2048 the
operations bound it: 512 op/byte against the chip's 240), over the
device time of the step's ``tpu_custom_call`` kernels.
"""
LAYER = "kernels"
SOURCE = "device_trace"
MOVES = "train_mfu"
DEVICE = True   # True: only a chip run can give it


def read(obs):
    t = obs.get("trace")
    if t is None or obs.get("kind") != "decoder_lm" or "job" not in obs \
            or not t.devices():
        return None
    from harness import flops
    secs = t.ops_seconds(lambda cat, _t: cat == "tpu_custom_call")
    if secs <= 0:
        return None
    job, p = obs["job"], obs["peaks"]
    cost = flops.flash_attention_cost(obs["cfg"], job["batch"], job["seq_len"])
    chips = obs["chips"]
    least = max(cost["flops"] / p["bf16_flops_per_s"],
                cost["bytes"] / p["hbm_bytes_per_s"]) / chips
    return 100.0 * least * obs["trace_steps"] / secs

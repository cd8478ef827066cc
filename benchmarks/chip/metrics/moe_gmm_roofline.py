"""The grouped-matmul kernel's share of its roofline: the least time the
held experts' SwiGLU could take for every record scored in the window
(their FLOPs at expected load at the bf16 peak, or their weights read
once a prefill batch at the HBM peak, whichever is longer), over the
summed device time of the ops named `moe_gmm`."""
from chipbench.work import peaks
from chipbench.work_mla_moe import (held_expert_flop_per_record,
                                    held_expert_weight_bytes)

KERNEL = "moe_gmm"


def read(run):
    if run.trace is None:
        return None
    t = run.trace.kernel_seconds(KERNEL)
    done = sum(a.scores.size for a in run.records if a.error is None)
    if t <= 0 or not done:
        return None
    m, traffic = run.cell.config, run.cell.traffic
    peak = peaks(run.device_kind)
    flops = done * held_expert_flop_per_record(m, int(traffic["seq_len"]))
    weight_bytes = (done / int(traffic["batch"])
                    * held_expert_weight_bytes(m))
    least = max(flops / peak["bf16_flop_per_s"],
                weight_bytes / peak["hbm_byte_per_s"])
    return 100.0 * least / t

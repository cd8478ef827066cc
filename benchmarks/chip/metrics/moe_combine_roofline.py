"""The MoE combine kernel's share of its roofline: the least time the
combine could take for every record scored in the window (each MoE
layer's held rows at expected load, k · held / E a token in bf16, read
once, and its float32 (tokens, d) output written once, at the HBM peak),
over the summed device time of the ops named `moe_combine`."""
from chipbench.work import peaks
from chipbench.work_mla_moe import held_load

KERNEL = "moe_combine"


def read(run):
    if run.trace is None:
        return None
    t = run.trace.kernel_seconds(KERNEL)
    done = sum(a.scores.size for a in run.records if a.error is None)
    if t <= 0 or not done:
        return None
    m, traffic = run.cell.config, run.cell.traffic
    tokens = int(traffic["seq_len"]) * (m["num_hidden_layers"]
                                         - m["first_k_dense_replace"])
    per_record = tokens * m["hidden_size"] * (held_load(m) * 2 + 4)
    least = done * per_record / peaks(run.device_kind)["hbm_byte_per_s"]
    return 100.0 * least / t

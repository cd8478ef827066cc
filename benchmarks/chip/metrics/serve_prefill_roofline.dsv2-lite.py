"""The DeepSeek-V2-Lite scoring program's share of the chip's bf16
compute roofline: the useful FLOPs of every record scored in the window
at peak, over the device time of the program `jit_serve_prefill`."""
from chipbench.work import peaks
from chipbench.work_mla_moe import prefill_flop_per_record

PROGRAM = "jit_serve_prefill"


def read(run):
    if run.trace is None:
        return None
    t = run.trace.program_busy_s(PROGRAM)
    done = sum(a.scores.size for a in run.records if a.error is None)
    if t <= 0 or not done:
        return None
    flops = done * prefill_flop_per_record(run.cell.config,
                                           int(run.cell.traffic["seq_len"]))
    return 100.0 * flops / peaks(run.device_kind)["bf16_flop_per_s"] / t

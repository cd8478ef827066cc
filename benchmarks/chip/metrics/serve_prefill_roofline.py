"""The scoring program's share of the chip's bf16 compute roofline: the
useful FLOPs of every record scored in the window (body, causal
attention, head at the last position) at peak, over the device time of
the program `jit_serve_prefill`. Beside `ingest.mfu`, which counts the
whole window, it separates the prefill's own efficiency from the time
the append path leaves the chip idle."""
from chipbench.work import llama_prefill_flop_per_record, peaks

PROGRAM = "jit_serve_prefill"


def read(run):
    if run.trace is None:
        return None
    t = run.trace.program_busy_s(PROGRAM)
    done = sum(a.scores.size for a in run.records if a.error is None)
    if t <= 0 or not done:
        return None
    flops = done * llama_prefill_flop_per_record(
        run.cell.config, int(run.cell.traffic["seq_len"]))
    return 100.0 * flops / peaks(run.device_kind)["bf16_flop_per_s"] / t

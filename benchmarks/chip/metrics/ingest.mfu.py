"""Whole ingest's share of the chip's bf16 peak: the useful FLOPs of
scoring a record (body, causal attention, head at the last position),
times records per second, over the peak."""
from chipbench.stats import rate
from chipbench.work import llama_prefill_flop_per_record, peaks


def read(run):
    done = sum(a.scores.size for a in run.records if a.error is None)
    if not done:
        return None
    per = llama_prefill_flop_per_record(
        run.cell.config, int(run.cell.traffic["seq_len"]))
    flops = per * rate(done, run.t_close - run.t0)
    return 100.0 * flops / peaks(run.device_kind)["bf16_flop_per_s"]

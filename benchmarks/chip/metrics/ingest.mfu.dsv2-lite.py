"""Whole ingest's share of the chip's bf16 peak for the DeepSeek-V2-Lite
scorer: the useful FLOPs of scoring a record on this chip
(`work_mla_moe.prefill_flop_per_record`), times records per second, over
the peak."""
from chipbench.stats import rate
from chipbench.work import peaks
from chipbench.work_mla_moe import prefill_flop_per_record


def read(run):
    done = sum(a.scores.size for a in run.records if a.error is None)
    if not done:
        return None
    per = prefill_flop_per_record(run.cell.config,
                                  int(run.cell.traffic["seq_len"]))
    flops = per * rate(done, run.t_close - run.t0)
    return 100.0 * flops / peaks(run.device_kind)["bf16_flop_per_s"]

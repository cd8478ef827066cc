"""Sketch kernel's share of its roofline over the appended shards: every
appended score read once at the chip's peak bandwidth, over the
kernel's summed device time."""
from chipbench.work import peaks, score_hist_bytes

KERNEL = "score_hist"


def read(run):
    if run.trace is None:
        return None
    t = run.trace.kernel_seconds(KERNEL)
    done = sum(a.scores.size for a in run.records if a.error is None)
    if t <= 0 or not done:
        return None
    need = score_hist_bytes(done)
    return 100.0 * need / peaks(run.device_kind)["hbm_byte_per_s"] / t

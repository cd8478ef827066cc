"""Emission kernel's share of its roofline: the HBM bytes the answers
need (every score read once, one index written per selected record)
at the chip's peak bandwidth, over the kernel's summed device time."""
from chipbench.work import peaks, threshold_select_bytes

KERNEL = "threshold_select"


def read(run):
    if run.trace is None:
        return None
    t = run.trace.kernel_seconds(KERNEL)
    done = [q for q in run.records if q.error is None]
    if t <= 0 or not done:
        return None
    scanned = len(done) * int(run.cell.config["records"])
    selected = sum(q.selected for q in done)
    need = threshold_select_bytes(scanned, selected)
    return 100.0 * need / peaks(run.device_kind)["hbm_byte_per_s"] / t

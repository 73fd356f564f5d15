"""Model FLOPs of the positions processed in the traced rounds (from
shapes, ``flops.span``), over the traced window times the chip's peak
bf16 rate."""
import flops
from readers import traced_rounds


def read(run):
    tr = run.get("trace")
    rounds = traced_rounds(run)
    if not tr or not rounds or not tr["n_devices"]:
        return None
    c = run["config"]
    work = sum(flops.span(c, lo, hi, k) for r in rounds
               for lo, hi, k in r["spans"])
    peak = flops.peaks(run["device"]["kind"])["bf16_flop_per_s"]
    return 100.0 * work / (tr["window_s"] * peak * tr["n_devices"])

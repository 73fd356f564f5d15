"""The least HBM traffic of the traced rounds (``hbm.round_bytes``, from
the config file and the program's ``sched.round`` counters), over the
traced window times the chip's peak HBM bandwidth."""
import flops
import hbm
import spans


def read(run):
    tr = run.get("trace")
    sp = spans.load(run)
    if not tr or sp is None or not tr["n_devices"]:
        return None
    counters = [s["counters"] for r, s in zip(run["rounds"], sp["rounds"])
                if r["traced"]]
    if not counters or any("kv_live_positions" not in k for k in counters):
        return None
    work = sum(hbm.round_bytes(run["config"], k) for k in counters)
    peak = flops.peaks(run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * work / (tr["window_s"] * peak * tr["n_devices"])

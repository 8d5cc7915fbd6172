"""Share of its roofline that the ``schedule_eval`` Pallas kernel reaches
in the traced batch: the least time its calls could take at the chip's
peak HBM bandwidth and FLOP rate, from the bytes and operations its
algorithm needs (``lib/kernels.py``), over the kernel's device time."""
import kernels
import peaks

# How the kernel's calls are named on the device's op line.
KERNEL = "schedule_delta_pallas"


def read(ctx):
    t = ctx["trace"]
    if not t or not ctx["device_kind"]:
        return None
    calls = [v for name, v in t["ops"].items() if KERNEL in name]
    seconds = sum(v["seconds"] for v in calls)
    count = sum(v["count"] for v in calls)
    if not count or seconds <= 0:
        return None
    cfg = ctx["cell"].config
    need = kernels.schedule_eval(cfg["instances"], cfg["sa"]["pop"],
                                 cfg["jobs"] * cfg["tasks_per_job"],
                                 cfg["horizon"])
    peak = peaks.of(ctx["device_kind"])
    least = max(need["bytes"] / peak["hbm_bytes_per_s"],
                need["flops"] / peak["bf16_flops_per_s"])
    return 100.0 * count * least / seconds

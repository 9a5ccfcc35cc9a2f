"""EXTRACT kernel: the least time the chip's HBM bandwidth allows for the
bytes the rounds needed (``perfbench.kernel_cost``), as a share of the
kernel's device time (%).  Parsing is integer vector work for which the
published peaks give no rate, so the bound is the HBM term alone."""

from perfbench import kernel_cost
from perfbench.layer_metrics.extract_kernel_ms import KERNEL


def read(ctx):
    seconds, calls = ctx["trace"].op_seconds(KERNEL)
    if not calls or seconds <= 0:
        return None
    e = ctx["config"]["engine"]
    t = ctx["config"]["table"]
    groups = int(e["max_groups"]) + 1 if int(e["max_groups"]) else 0
    need = kernel_cost.extract_bytes(
        ctx["tuples_scanned"], calls, record_bytes=ctx["record_bytes"],
        workers=int(e["num_workers"]), budget=int(e["budget"]),
        slots=int(ctx["config"]["server"]["max_slots"]),
        cols=int(t["num_cols"]), groups=groups)
    bw = kernel_cost.peaks(ctx["device_kind"])["hbm_bytes_per_s"]
    return need / bw / seconds * 100.0

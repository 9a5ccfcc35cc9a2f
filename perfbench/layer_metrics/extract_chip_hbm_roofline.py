"""EXTRACT kernel on a table sharded over the chips: the least time one
chip's HBM bandwidth allows for that chip's share of the bytes the rounds
needed (``perfbench.kernel_cost`` for its rows and its workers), as a
share of the kernel's device time on a chip (%).  Rows, workers and
kernel time are averaged over the ``mesh_devices`` chips, each of which
extracts from its own chunk range."""

from perfbench import kernel_cost
from perfbench.layer_metrics.extract_kernel_ms import KERNEL


def read(ctx):
    # op_seconds averages the time and the calls over the chips
    seconds, calls = ctx["trace"].op_seconds(KERNEL)
    if not calls or seconds <= 0:
        return None
    e = ctx["config"]["engine"]
    t = ctx["config"]["table"]
    chips = int(ctx["config"].get("mesh_devices", 1))
    groups = int(e["max_groups"]) + 1 if int(e["max_groups"]) else 0
    need = kernel_cost.extract_bytes(
        ctx["tuples_scanned"] / chips, calls,
        record_bytes=ctx["record_bytes"],
        workers=int(e["num_workers"]) // chips, budget=int(e["budget"]),
        slots=int(ctx["config"]["server"]["max_slots"]),
        cols=int(t["num_cols"]), groups=groups)
    bw = kernel_cost.peaks(ctx["device_kind"])["hbm_bytes_per_s"]
    return need / bw / seconds * 100.0

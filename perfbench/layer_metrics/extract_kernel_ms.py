"""EXTRACT kernel: device time of the fused Pallas kernel per round (ms),
found by the kernel's name."""

# the fused kernels are the program's slot_extract* Pallas calls
KERNEL = r"slot_extract"


def read(ctx):
    seconds, calls = ctx["trace"].op_seconds(KERNEL)
    if not calls or not ctx["rounds"]:
        return None
    return seconds / ctx["rounds"] * 1e3

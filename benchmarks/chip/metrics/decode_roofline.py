"""The batched decode step's share of its byte roofline.  For each step
traced, the least time of what the step must move at the chip's HBM
bandwidth (``decode_step_bytes`` of the architecture's module, at the
positions of the serve loop's ``decode`` notes; for zamba2 every weight read
once, each active slot's SSM and conv state read and written, and each
hybrid invocation's KV read up to the slot's position with one row
written).  The share is the summed least time over the device time of the
engine's fused step (``jit_step``); an architecture with no such count
reads nothing."""
import spec


def read(ctx):
    if ctx.transport != "cache_handoff":
        return None
    count = getattr(spec.arch_module(ctx.model.arch), "decode_step_bytes",
                    None)
    steps = [c["pos"] for c in ctx.calls if c["kind"] == "decode"]
    secs = ctx.trace.module_s("jit_step")
    if count is None or not steps or secs <= 0:
        return None
    least = sum(count(ctx.model, pos) for pos in steps) / \
        ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / secs

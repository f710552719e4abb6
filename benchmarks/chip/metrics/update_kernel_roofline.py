"""Share of its roofline the packed update kernel reaches: the least time
the chip could take for one step's update, max(FLOPs / peak FLOP/s,
bytes / peak bytes/s) from counts.py, over the kernel's device time per
step, in percent. Says on an earlier line which bound applies."""

KERNEL = "fsgld_update_packed"


def read(ctx):
    if ctx.trace is None:
        return None
    s = ctx.trace.op_s(KERNEL)
    if s <= 0:
        return None
    per_step = s / (ctx.window.rounds * ctx.steps_per_round)
    t_flops = ctx.update_flops / ctx.peaks["bf16_flops_per_s"]
    t_bytes = ctx.update_bytes / ctx.peaks["hbm_bytes_per_s"]
    bound = "memory" if t_bytes >= t_flops else "compute"
    ctx.log(f"update_kernel_roofline: {bound}-bound; {ctx.update_bytes:.6g}"
            f" bytes and {ctx.update_flops:.6g} FLOPs per step; kernel "
            f"{per_step * 1e3:.6f} ms per step")
    return 100.0 * max(t_flops, t_bytes) / per_step

"""spin_image_roofline: the spin-image kernel's share of its roofline:
the least time the chip could take for the algorithm's operations and
bytes (``work/spin_image.py``) at the published peaks, over the kernel's
device time in the trace, in percent."""


def read(ctx):
    k = (ctx.trace or {}).get("kernels", {}).get("spin_image")
    if not k or k["seconds"] <= 0:
        return None
    _, _, least = ctx.kernel_work("spin_image", ctx.traced_loops)
    return 100.0 * least / k["seconds"]

"""mandelbrot_roofline: the Mandelbrot kernel's share of its roofline:
the least time the chip could take for the escape iterations the image
needs and its bytes (``work/mandelbrot.py``) at the published peaks,
over the kernel's device time in the trace, in percent."""


def read(ctx):
    k = (ctx.trace or {}).get("kernels", {}).get("mandelbrot")
    if not k or k["seconds"] <= 0:
        return None
    _, _, least = ctx.kernel_work("mandelbrot", ctx.traced_loops)
    return 100.0 * least / k["seconds"]

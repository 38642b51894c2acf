"""loop_s: the window's seconds over the loops completed in it, i.e. the
mean makespan (api.build + api.run) of the parallel loop under the
cell's perturbation, with the task bodies on the device."""


def read(ctx):
    return ctx.window_s / len(ctx.loops)

"""dup_task_share: task executions of rDLB re-issued chunks
(``chunk.duplicate``) as a percentage of the loop's N tasks, over the
loops of the window.  Work a fail-stop worker dies holding is not
executed and not counted."""


def read(ctx):
    dup = sum(stop - start for rec in ctx.loops
              for start, stop, duplicate in rec.calls if duplicate)
    return 100.0 * dup / (ctx.cfg["n_tasks"] * len(ctx.loops))

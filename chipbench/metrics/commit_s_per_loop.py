"""commit_s_per_loop: host seconds in the ``backend.commit`` spans (the
program's ``ChunkBackend.commit``, under the engine's commit lock) per
traced loop, from the profiler trace."""


def read(ctx):
    tr = ctx.trace
    if not tr or not tr["loops"] or "backend.commit" not in tr["span_s"]:
        return None
    return tr["span_s"]["backend.commit"] / tr["loops"]

"""Device time per outer round of the wide epoch plan: the ops whose
innermost named scope is `pscope.plan_wide` (the plan that
`core/plan.py` builds where its packed int32 key would overflow), less
the kernels' and the collectives' time.  `bench/phases.py` reduces a
fixed list of scopes, so the reduction is made here, from the ops that
`phases.load` reads with their op_name paths, inside the traced
window.  A trace with no op of the scope reports nothing."""
import functools
import os

import phases
import tracereduce

SCOPE = "pscope.plan_wide"


def scope_ns(trace: dict, plane: str) -> float:
    """The union of the scope's ops on one chip inside the window, less
    the union of the kernels and the collectives."""
    lo, hi = tracereduce.window_of(trace, phases.WINDOW)
    ops = [[o[0], max(o[1], lo), min(o[2], hi), *o[3:]]
           for o in trace["devices"].get(plane, []) if o[2] > lo and o[1] < hi]
    out = [tuple(x) for x in tracereduce.union(
        [(o[1], o[2]) for o in ops if phases._excluded(o[0], o[3])])]
    mine = [(o[1], o[2]) for o in ops
            if len(o) > 4 and phases.scope_of(o[4]) == SCOPE]
    if not mine:
        return 0.0
    return (tracereduce._length(tracereduce.union(mine + out))
            - tracereduce._length(out))


@functools.lru_cache(maxsize=2)
def _trace(path: str, mtime_ns: int) -> dict:
    return phases.load(path)


def read(ctx):
    path = tracereduce.find_xplane(phases.TRACE_DIR)
    trace = _trace(path, os.stat(path).st_mtime_ns)
    ns = scope_ns(trace, ctx["chip"]["plane"])
    return ns / ctx["rounds"] / 1e6 if ns else None

"""``utils/dispatch`` ``dispatch_count`` (jit calls, eager primitives,
device_get) over the window per completed query. Nothing where the
counters were not installed (an untraced run)."""


def read(run):
    done = sum(1 for q in run["window"]["queries"] if q["ok"])
    if not done or run["dispatch"] is None:
        return None
    return run["dispatch"]["dispatch_count"] / done

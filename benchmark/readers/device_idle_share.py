"""1 minus the union of the intervals on the devices' "XLA Modules" lines
over the traced rounds, in percent."""


def read(run):
    trace = run["trace"]
    if not trace or not trace.get("busy_s"):
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])

"""Counters of the program (``utils/tracing.count``: which way it decided,
a batch or a partition at a time) over the window per completed query:
every counter that ``utils/dispatch.delta()`` carries under ``counters``
whose name is ``prefix`` or starts with ``prefix`` and a ``.``, summed.
Nothing where the counters were not installed (an untraced run) or the
program carries none (before PR 30); 0 where it carries counters and none
of these moved."""


def read(run, prefix):
    done = sum(1 for q in run["window"]["queries"] if q["ok"])
    counters = (run["dispatch"] or {}).get("counters")
    if not done or counters is None:
        return None
    return sum(n for name, n in counters.items()
               if name == prefix or name.startswith(prefix + ".")) / done

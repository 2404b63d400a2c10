"""Host seconds the program spent in some of its spans outside the
window: set-up (the cache fill, the first calls) and what followed the
window. The process's whole span table, read here in the same process
(``utils/dispatch.snapshot()["spans"]``), less the window's
(``dispatch.delta()``'s ``spans``).

``spans`` names the rows to sum by prefix (a prefix matches a whole name
or what precedes a ``.``); ``field`` is ``total_s`` or ``self_s``.
Seconds are summed over threads. Nothing where the counters were not
installed (an untraced run), or where no row matches: a program that
keeps no such span."""


def matches(name: str, prefixes) -> bool:
    return any(name == p or name.startswith(p + ".") for p in prefixes)


def read(run, field, spans):
    if run["dispatch"] is None:
        return None
    from spark_rapids_tpu.utils import dispatch

    window = run["dispatch"]["spans"]
    rows = [(row, window.get(name)) for name, row
            in dispatch.snapshot()["spans"].items() if matches(name, spans)]
    if not rows:
        return None
    return sum(row[field] - (w[field] if w else 0.0) for row, w in rows)

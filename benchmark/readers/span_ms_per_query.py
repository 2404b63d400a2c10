"""Host milliseconds a completed query of the window spent in some of the
program's spans: rows of ``utils/tracing``'s table, which
``utils/dispatch.delta()`` carries as ``spans`` (``{name: {"count",
"total_s", "self_s"}}`` over the window).

``spans`` names the rows to sum by prefix; ``except`` instead sums every
row but those (a prefix matches a whole name or what precedes a ``.``).
``field`` is ``total_s`` (a span's whole duration) or ``self_s`` (less
what its children on the same thread cover, launch timers included).
Seconds are summed over threads. Nothing where the counters were not
installed (an untraced run) or the program keeps no span table (before
PR 25)."""


def matches(name: str, prefixes) -> bool:
    return any(name == p or name.startswith(p + ".") for p in prefixes)


def read(run, field, spans=None, **other):
    done = sum(1 for q in run["window"]["queries"] if q["ok"])
    table = (run["dispatch"] or {}).get("spans")
    if not done or table is None:
        return None
    if spans is not None:
        rows = [r for n, r in table.items() if matches(n, spans)]
    else:
        rows = [r for n, r in table.items()
                if not matches(n, other["except"])]
    return 1e3 * sum(r[field] for r in rows) / done

"""A statement's share of the memory roofline, in percent.

Least time: the table's rows times the declared widths (configuration
file) of the columns the statement's text names, over the chip's HBM
bytes/s: the statements here read every row of those columns once and
compute a few operations a value, so they are bandwidth-bound. Measured
time: the union of device-busy intervals inside that statement's queries
in the trace, per query. The bytes come from the statement and the table,
never from a program's operands. Nothing where the trace has no device
time for the statement: never 0."""
import re


def least_bytes(run, statement) -> int:
    words = set(re.findall(r"[A-Za-z_][A-Za-z_0-9]*",
                           run["statements"][statement]))
    total = 0
    for name, table in run["config"]["tables"].items():
        if name not in words:
            continue
        width = sum(c["declared_bytes"] for c in table["columns"]
                    if c["name"] in words)
        total += run["rows"][name] * width
    return total


def read(run, statement):
    trace = run["trace"]
    if not trace or not run["peaks"] \
            or not trace.get("statement_busy_s", {}).get(statement):
        return None
    least_s = least_bytes(run, statement) / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / trace["statement_busy_s"][statement]

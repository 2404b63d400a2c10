"""From a profiler trace (``.xplane.pb``) to device busy and idle time,
per-module device time, device time per statement, and idle gaps named by
the host span that covers them.

Reads the file with ``jax.profiler.ProfileData`` and nothing else. What it
relies on in the trace (seen by hand in PR 23's traces of this engine on a
v5e, jax 0.9.0):

- one plane a chip, ``/device:TPU:<n>``, whose line ``XLA Modules`` holds
  one event per executed program (``jit_<name>(<fingerprint>)``); the
  union of those intervals is the time an operation ran on that chip;
- the plane ``/host:CPU`` holds one line a host thread; spans written by
  ``jax.profiler.TraceAnnotation`` (the benchmark's ``bench.*`` and the
  program's ``TraceRange`` names) and JAX's own (``PjitFunction(..)``,
  ``DevicePut``) lie there on the same clock as the device events; names
  that start with ``$`` are the Python tracer's frames and are left out.

The window is the span from the first ``bench.query.*`` host span's start
to the last one's end; device events are clipped to it.
"""
from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
MODULE_LINE = "XLA Modules"
QUERY_SPAN = "bench.query."


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def stable_name(module: str) -> str:
    """``jit_fused_chain_decode_filter_project__4651(28020..)`` and its
    siblings of other chain hashes are one row of the breakdown."""
    name = re.sub(r"\(\d+\)$", "", module)
    return re.sub(r"__[0-9a-f]{4}$", "", name)


def union(intervals) -> list:
    """Sorted, merged [start, end] of (start, end) pairs."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def covered(merged, lo, hi) -> float:
    """Length of ``merged`` (from ``union``) inside [lo, hi]."""
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in merged)


def read_planes(path: str):
    """({chip: [(start_ns, end_ns, module name)]},
    [(start_ns, end_ns, span name)] of the host)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    chips, host = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == MODULE_LINE:
                    chips[int(m.group(1))] = [
                        (e.start_ns, e.start_ns + e.duration_ns, e.name)
                        for e in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend((e.start_ns, e.start_ns + e.duration_ns, e.name)
                            for e in line.events
                            if not e.name.startswith("$"))
    return chips, host


def name_gaps(gaps, host) -> dict:
    """{span name: idle seconds * 1e9}: every gap goes to the shortest host
    span that covers at least half of it. One sweep in time order over
    ``host`` [(start, end, name)] with the spans still open kept aside."""
    host = sorted(host)
    out, open_, i = {}, [], 0
    for lo, hi in sorted(gaps):
        while i < len(host) and host[i][0] < hi:
            open_.append(host[i])
            i += 1
        open_ = [sp for sp in open_ if sp[1] > lo]
        best, best_len = "no host span", None
        for s, e, name in open_:
            if min(e, hi) - max(s, lo) >= 0.5 * (hi - lo) \
                    and (best_len is None or e - s < best_len):
                best, best_len = name, e - s
        out[best] = out.get(best, 0.0) + (hi - lo)
    return out


def reduce(path: str, top: int = 10) -> dict:
    """Everything the readers take from a trace; seconds throughout.
    Raises where the trace holds no ``bench.query.*`` span; returns
    ``chips: 0`` where it holds no device plane (a CPU run)."""
    chips, host = read_planes(path)
    queries = [(s, e, n[len(QUERY_SPAN):]) for s, e, n in host
               if n.startswith(QUERY_SPAN)]
    if not queries:
        raise ValueError(f"{path}: no {QUERY_SPAN}* span in the trace")
    lo = min(s for s, _, _ in queries)
    hi = max(e for _, e, _ in queries)
    out = {"chips": len(chips), "window_s": (hi - lo) / 1e9,
           "queries_traced": len(queries)}
    if not chips:
        return out

    busy, modules, gap_names, per_statement = [], {}, {}, {}
    for events in chips.values():
        inside = [(max(s, lo), min(e, hi), n) for s, e, n in events
                  if e > lo and s < hi]
        merged = union((s, e) for s, e, _ in inside)
        busy.append(sum(e - s for s, e in merged))
        for s, e, n in inside:
            key = stable_name(n)
            modules[key] = modules.get(key, 0.0) + (e - s)
        edges = [lo] + [x for pair in merged for x in pair] + [hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        for name, ns in name_gaps(gaps, host).items():
            gap_names[name] = gap_names.get(name, 0.0) + ns
        for s, e, stmt in queries:
            per_statement.setdefault(stmt, []).append(covered(merged, s, e))
    # host seconds by span name, for detail.json: which of the program's
    # spans the traced rounds spent their time in (spans nest and threads
    # overlap, so these do not add up to the window)
    totals = {}
    for s, e, name in host:
        if e > lo and s < hi:
            totals[name] = totals.get(name, 0.0) + (min(e, hi) - max(s, lo))
    out["host_spans"] = [[k, v / 1e9] for k, v in sorted(
        totals.items(), key=lambda kv: -kv[1])[:25]]
    n = len(chips)
    out["busy_s"] = sum(busy) / n / 1e9
    out["device_ops"] = [[k, v / n / 1e9] for k, v in sorted(
        modules.items(), key=lambda kv: -kv[1])[:top]]
    out["idle_gaps"] = [[k, v / n / 1e9] for k, v in sorted(
        gap_names.items(), key=lambda kv: -kv[1])[:top]]
    # device seconds inside each statement's queries, mean per query
    # (chips add up: the work of a query is what all its chips did)
    out["statement_busy_s"] = {
        stmt: sum(v) / 1e9 / (len(v) / n) for stmt, v in per_statement.items()}
    out["statement_queries"] = {
        stmt: len(v) // n for stmt, v in per_statement.items()}
    return out

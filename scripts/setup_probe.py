"""Where a benchmark run's set-up went, by the program's own spans.

    python3 scripts/setup_probe.py --workload <cell> --seed <n> --seconds <s> --trace 1

Runs ``benchmark/run.py``'s main unchanged, with each ``CompileMeter``
snapshot (before ``prepare``, after the first calls, around the window) and
the start of the first calls also taking the program's set-up counters and
spans (``cache.*``, ``ScanExec.*``, ``strings.dictionary``, ``compile.*``).
Prints the harness's two lines, then ``{"probe": [...]}``: one entry a
snapshot, in order, each with its ``tag``. The difference of two entries
splits set-up into the fill (and the programs its ``count()`` loads) and
the first calls. Needs ``--trace 1``: the spans are recorded only then.
"""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
from benchmark import meter, run  # noqa: E402
from spark_rapids_tpu.utils import tracing  # noqa: E402

KEEP = ("cache.", "ScanExec.", "strings.dictionary", "compile.")
snaps = []


def take(tag, extra=None):
    snaps.append(dict(extra or {}, tag=tag, counters={
        k: v for k, v in tracing.counters().items() if k.startswith(KEEP)},
        spans={k: [v["count"], round(v["total_s"], 4)]
               for k, v in tracing.table().items() if k.startswith(KEEP)}))


class Meter(meter.CompileMeter):
    def snapshot(self):
        take("meter", {"meter": list(super().snapshot())})
        return super().snapshot()


real_round = run.Loop.round


def first_round_marked(self):
    if not self.queries:
        take("first_call_start")
    return real_round(self)


def main() -> int:
    meter.CompileMeter = Meter
    run.Loop.round = first_round_marked
    rc = run.main(sys.argv[1:])
    take("end")
    print(json.dumps({"probe": snaps}), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())

"""``correct`` has to come out false when the timed path is broken
underneath. Each test skips the harness's look for a chip (a rehearsal at
sf 0.02 on the CPU) and drives the rest of a run: set-up, window, the plain
reference, the comparison. The faults a cell of this system can have: rows
left out of what a statement reads (a batch of the cache; half of the
scan's splits, with the mix's ``prepare`` emptied so that the statements
read the files: the regime a later scan cell will time), and an answer
altered where it is produced. A step that returns
its state unchanged and an exchange between chips left out have nothing to
stand for here: no cell keeps state from query to query, none spans chips.
"""
import pytest

from benchmark import run

SF = 0.02
SEED = 2**31 + 7


CELL = "tpch-sf1.cached-q1q6"


def drive(cached=True):
    spec = run.load_cell(CELL)
    if not cached:
        spec["mix"] = {**spec["mix"], "prepare": {"cache": []}}
    result, _ = run.run_cell(spec, SEED, 0.3, False, rehearse_sf=SF)
    return result


@pytest.mark.parametrize("cached", [True, False])
def test_sound_run_is_correct(cached):
    result = drive(cached)
    assert result["correct"], result["compared"]
    assert result["failed"] == 0 and result["attempted"] >= 2


def test_half_of_the_scan_left_out(monkeypatch):
    from spark_rapids_tpu.io.parquet import ParquetSource

    whole = ParquetSource._build_splits
    monkeypatch.setattr(ParquetSource, "_build_splits",
                        lambda self: whole(self)[::2])
    result = drive(cached=False)
    assert not result["correct"], result["compared"]
    # counts are exact: fewer rows is a mismatch, not a rounding
    assert result["compared"]["q1.mismatches"]["value"] > 0


def test_a_cached_batch_left_out(monkeypatch):
    from spark_rapids_tpu.execs.cache import CacheHolder

    whole = CacheHolder.batches
    calls = []

    def partial(self, p):
        # the fill's own count is sound; every query after it loses a batch
        calls.append(p)
        return whole(self, p) if len(calls) == 1 else whole(self, p)[1:]

    monkeypatch.setattr(CacheHolder, "batches", partial)
    result = drive(cached=True)
    assert not result["correct"], result["compared"]


@pytest.mark.parametrize("cached", [True, False])
@pytest.mark.parametrize("factor", [1 + 1e-6])
def test_an_answer_altered_where_it_is_produced(monkeypatch, cached, factor):
    from spark_rapids_tpu.api.dataframe import DataFrame

    sound = DataFrame.collect

    def altered(self):
        frame = sound(self)
        col = frame.columns[-1] if len(frame.columns) == 1 else "sum_charge"
        if col in frame.columns:
            frame = frame.copy()
            frame.loc[0, col] = frame.loc[0, col] * factor
        return frame

    monkeypatch.setattr(DataFrame, "collect", altered)
    result = drive(cached)
    assert not result["correct"], result["compared"]
    worst = max(v["value"] for k, v in result["compared"].items()
                if k.endswith("max_rel_err"))
    assert worst > 1e-7

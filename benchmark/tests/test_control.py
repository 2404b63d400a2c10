"""The control of ``correct``: the plain reference computed in float32, the
precision below the float64 the configurations state, put in the engine's
place, has to come out as not correct. Here at a size a test run can hold
(sf 0.02, 120,000 rows); at the cells' own sizes on the chip machine the
readings are in PERF.md. Needs no engine and no chip."""
import numpy as np
import pytest

from benchmark import run

SF = 0.02


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 5])
@pytest.mark.parametrize("cell", ["tpch-sf1.cached-q1q6",
                                  "tpch-sf10.cached-q1q6"])
def test_float32_reference_is_not_correct(cell, seed):
    spec = run.load_cell(cell)
    tables, _, _ = run.ensure_data(spec, seed, SF)
    ok64, compared64, _ = run.judge(spec, tables, None, dtype=np.float64)
    assert ok64, compared64
    ok32, compared32, _ = run.judge(spec, tables, None, dtype=np.float32)
    assert not ok32, compared32
    over = [k for k, v in compared32.items()
            if v["limit"] is not None and v["value"] > v["limit"]]
    assert any(k.endswith("max_rel_err") for k in over), compared32

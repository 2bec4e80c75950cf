"""The ``slot_hit_share`` reader on the program's counters, and in a
traced run of a small warm cell."""
import time

import pytest

import harness
from metrics import slot_hit_share


@pytest.fixture
def obs():
    import repro.obs
    repro.obs.disable()
    repro.obs.reset()
    yield repro.obs
    repro.obs.disable()
    repro.obs.reset()


def _ctx():
    return harness.Context(products=2, spans={}, trace=None, work=[],
                           peaks=None)


def test_reader_reads_the_two_counters(obs):
    assert slot_hit_share.read(_ctx()) is None        # a program without them
    obs.enable(reset=True)
    assert slot_hit_share.read(_ctx()) is None
    for name in ("spgemm.numeric.slot_hits",) * 3 + (
            "spgemm.numeric.slot_searches",):
        obs.metrics.inc(name)
    obs.disable()
    assert slot_hit_share.read(_ctx()) == {"value": 0.75, "hits": 3,
                                           "searches": 1}


def test_traced_warm_run_reads_every_call_a_hit(tmp_path, obs):
    cell = harness.load_cell("bcsstk32.warm")
    cell.config.update({"n": 1500, "nnz": 20_000, "sigma": 4.0,
                        "ell_k": 32})
    res = harness.run(cell, 5, 0.1, True, time.perf_counter(),
                      trace_dir=tmp_path)
    assert res["correct"], res["checks"]
    got = res["metrics"]["slot_hit_share"]
    assert got["unit"] == "fraction"
    assert got["value"] == 1.0 and got["hits"] >= 2 and got["searches"] == 0

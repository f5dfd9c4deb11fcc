"""``correct`` has been shown to fail: the float32 control comes out not
correct, and a run whose timed path is broken underneath comes out not
correct, for each fault this kind of cell can have (an answer altered where
it is produced; rows left out; an answer finished on the host).  The runs
skip the harness's look for a chip (``scale_factor``) and drive the rest of
a run at a size a test can hold."""

import json
import os
import time

import pytest

import harness

with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
    CELLS = [w["name"] for w in json.load(f)["workloads"]]   # every cell
SF = 0.05   # 300,000 lineitem rows


@pytest.mark.parametrize("seed", [11, 2200000033, 4294967295])
@pytest.mark.parametrize("cell", CELLS)
def test_float32_control_is_not_correct(cell, seed):
    from tools.control import control_verdict
    v = control_verdict(cell, seed, scale_factor=SF)
    assert not v["correct"]
    n = v["numbers"]
    assert n["wrong_answers"]["value"] == 0       # only the precision differs
    assert n["max_rel_gap"]["value"] > 3 * n["max_rel_gap"]["limit"]


def _nudge(rows):
    """The first float of the first row, moved by one part in a million."""
    first = list(rows[0])
    i = next(i for i, v in enumerate(first) if isinstance(v, float))
    first[i] *= 1 + 1e-6
    return [tuple(first)] + list(rows[1:])


FAULTS = {
    "sound": None,
    "answer_altered": lambda rows, session: _nudge(rows),
    "rows_left_out": lambda rows, session: rows[:-1],
    "finished_on_host": lambda rows, session: (session.last_metrics.update(
        partitionFallbackCount=1), rows)[1],
}


@pytest.mark.parametrize("fault", list(FAULTS))
@pytest.mark.parametrize("cell", CELLS)
def test_broken_timed_path_is_not_correct(cell, fault, monkeypatch):
    from spark_rapids_tpu.dataframe import DataFrame
    real, calls = DataFrame.collect, []
    mix = harness.load_cell(cell)["mix"]
    warm_up = mix.get("text_submissions_in_setup", 0) + 2

    def collect(self):
        rows = real(self)
        calls.append(1)
        # the warm-up's answers stay sound: the fault is in the window's
        if FAULTS[fault] is not None and len(calls) > warm_up:
            rows = FAULTS[fault](rows, self.session)
        return rows

    monkeypatch.setattr(DataFrame, "collect", collect)
    result = harness.run_cell(cell, seed=2200000033, seconds=0.5, trace=False,
                              t_start=time.monotonic(), scale_factor=0.002)
    assert result["attempted"] >= 1
    assert result["correct"] is (fault == "sound"), result["compared"]
    assert all(name.startswith("rehearsal.") for name in result["metrics"])


@pytest.mark.parametrize("statement", ["held", "text"])
def test_the_window_sends_what_the_mix_says(statement, monkeypatch):
    """``held``: one ``session.sql`` in set-up (after the text submissions),
    then ``collect()`` on that DataFrame; ``text``: ``session.sql(text)``
    again for every query, nothing held."""
    from spark_rapids_tpu.session import TpuSparkSession
    spec = harness.load_cell(CELLS[0])
    spec["mix"] = dict(spec["mix"], statement=statement)
    monkeypatch.setattr(harness, "load_cell", lambda name: spec)
    real, planned = TpuSparkSession.sql, []
    monkeypatch.setattr(TpuSparkSession, "sql", lambda self, text: (
        planned.append(text), real(self, text))[1])
    result = harness.run_cell(CELLS[0], seed=7, seconds=0.5, trace=True,
                              t_start=time.monotonic(), scale_factor=0.002)
    texts = spec["mix"]["text_submissions_in_setup"]
    sent = texts + 2 + result["attempted"]
    assert len(planned) == (texts + 1 if statement == "held" else sent)
    assert result["correct"], result["compared"]
    assert result["metrics"]["rehearsal.resubmit_compiles"]["value"] >= 0

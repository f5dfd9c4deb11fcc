"""chip_smoke.py rehearsed in-process on the CPU at a tiny scale: every
phase runs and prints one parseable JSON line, the verdict is
``"ok": false`` with a non-zero exit because the platform is not ``tpu``
(a CPU run is never reported as a chip run), and a reference that
disagrees fails the phase instead of being reported."""

import importlib.util
import json
import os

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(_ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ARGS = ["--sf", "0.002", "--queries", "q6"]


def test_cpu_rehearsal_runs_every_phase_and_refuses_success(
        chip_smoke, capsys):
    rc = chip_smoke.main(ARGS)
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    assert rc != 0
    assert set(lines[-1]) == {"ok", "device"} and lines[-1]["ok"] is False
    assert lines[-1]["device"]["platform"] == "cpu"
    lines = lines[:-1]
    phases = [ln["phase"] for ln in lines]
    assert phases == ["env", "dropped", "datagen", "query", "query", "device",
                      "cache", "total"]
    q6, like = (ln for ln in lines if ln["phase"] == "query")
    assert q6["query"] == "q6" and q6["rows"] == 1 and q6["equal"]
    for q in (q6, like):
        assert q["warmCompileCount"] == 0
        assert all(q[k] == 0 for k in chip_smoke.MUST_BE_ZERO)
    # the one default-on Pallas kernel engaged (interpreted off the chip)
    assert like["pallasKernels"] == ["strings"]
    dev = next(ln for ln in lines if ln["phase"] == "device")
    assert dev["cachedInputs"]["batches"] >= 1


def test_reference_mismatch_fails_the_phase(chip_smoke, monkeypatch,
                                            capsys):
    real = chip_smoke.REFERENCES["q6"]
    monkeypatch.setitem(chip_smoke.REFERENCES, "q6",
                        lambda t: [(real(t)[0][0] * 1.01,)])
    with pytest.raises(AssertionError, match="q6: device checksum"):
        chip_smoke.main(ARGS)
    out = capsys.readouterr().out
    assert '"ok"' not in out  # no verdict line after a failed phase

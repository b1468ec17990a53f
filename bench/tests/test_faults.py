"""A sound tiny run comes out correct, and a traced one spans every step;
one whose decode step returns its state unchanged does not."""
import pytest

from bench import registry
from bench.tests import faults


@pytest.fixture
def quick(monkeypatch):
    faults.quick(monkeypatch)


def test_sound_run_is_correct(quick, tmp_path):
    out = faults.run(tmp_path, 2 ** 31 + 11)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    # with --trace 0, exactly the cell's end-to-end metrics
    cell = registry.cell("yi-6b-16l.chat-steady")
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}


def test_state_unchanged_is_not_correct(quick, tmp_path, monkeypatch):
    faults.state_unchanged(monkeypatch)
    out = faults.run(tmp_path, 2 ** 31 + 12)
    assert not out["correct"], out["checks"]


def test_traced_run_spans_every_step(quick, tmp_path):
    from bench import trace as T

    out = faults.run(tmp_path, 2 ** 31 + 13, trace=True)
    assert out["correct"], out["checks"]
    tr = T.load(T.find_file(str(tmp_path / "trace" /
                                "yi-6b-16l.chat-steady")))
    steps = [e for e in tr.spans if e.name == "ElisServer.step"]
    execs = [e for e in tr.spans if e.name == "EngineExecutor.execute"]
    assert steps and execs
    # every window runs inside a step, so idle gaps in the frontend and
    # scheduler are filed under the step and not under "none"
    assert all(any(s.start <= e.start and e.end <= s.end for s in steps)
               for e in execs)

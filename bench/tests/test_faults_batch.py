"""Tiny runs with half of each decode batch left out, or with a token
altered where it is produced, come out not correct."""
import pytest

from bench.tests import faults


@pytest.fixture
def quick(monkeypatch):
    faults.quick(monkeypatch)


@pytest.mark.parametrize("fault", [faults.half_batch, faults.token_altered],
                         ids=["half_batch", "token_altered"])
def test_broken_path_is_not_correct(quick, tmp_path, monkeypatch, fault):
    fault(monkeypatch)
    out = faults.run(tmp_path, 2 ** 31 + 12)
    assert not out["correct"], out["checks"]

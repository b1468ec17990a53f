"""The control comes out not correct: at a tiny size on the CPU, the
reference computed in fp8 in the program's place reads a logit gap above
the configuration's limit on the same served sample, where the program's
own tokens read below it."""
import gc

from bench import harness, registry, traffic
from bench.tests import faults, tiny


def test_fp8_control_fails_the_limit(monkeypatch, tmp_path):
    faults.quick(monkeypatch)
    cell = tiny.cell("yi-6b-16l.chat-steady", check_requests=12,
                     drain_cap_s=5.0)
    cell.cfg["serving"].update(slots=4, knee_rps=16.0)
    seed = 2 ** 31 + 21
    reqs = traffic.schedule(cell.traffic, harness.rate(cell), [0.5, 2.0],
                            seed)
    sys_ = harness.prepare(cell, seed, 16)
    rec = harness.Record(cfg=cell.cfg, peaks=registry.peaks("TPU v5 lite"))
    handles = harness.drive(sys_, reqs, 0.5, 2.0, 5.0, rec)
    served = harness.served_tokens(handles)
    del handles
    sys_.server = sys_.executor = sys_.engine = None
    gc.collect()
    sound = harness.checks(sys_, reqs, served, 12)
    ctrl = harness.checks(sys_, reqs, served, 12, control=True)
    assert harness.passed(sound), sound
    assert not harness.passed(ctrl), ctrl
    assert ctrl["logit_gap"]["value"] > 3 * sound["logit_gap"]["value"]

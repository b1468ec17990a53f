"""Readings that set the limit of the logit-gap comparison, at a cell's
own size on the chip: for each seed, serve the cell's traffic for a short
window at its own load, drain it, and on the same sample of finished
requests read the program's widest gap and the control's (the reference
in fp8 in the program's place).

    python -m bench.control --workload qwen2-1.5b.chat-steady \\
        --seconds 10 --seeds 11 12 13

The benchmark's own runs never run the control.  One JSON line per seed.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    import jax

    from bench import harness, registry, traffic
    from bench.run import require_chips
    from repro.launch.compile_cache import enable_compile_cache

    cell = registry.cell(args.workload)
    require_chips(cell.chips)
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    dev = harness.device_info()
    peaks = registry.peaks(dev["kind"])
    tr = cell.traffic
    w0, w1, drain = harness.measured_window(tr, args.seconds)
    for seed in args.seeds:
        reqs = traffic.schedule(tr, harness.rate(cell), [w0, w1], seed)
        sys_ = harness.prepare(cell, seed, len(reqs))
        rec = harness.Record(cfg=cell.cfg, peaks=peaks)
        handles = harness.drive(sys_, reqs, w0, w1, drain, rec)
        served = harness.served_tokens(handles)
        del handles
        sys_.server = sys_.executor = sys_.engine = None
        gc.collect()
        n = int(tr["check_requests"])
        sound = harness.checks(sys_, reqs, served, n)
        ctrl = harness.checks(sys_, reqs, served, n, control=True)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "program": sound, "control": ctrl,
                          "device": dev}), flush=True)
        del sys_
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())

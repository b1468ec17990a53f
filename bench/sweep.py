"""Find a configuration's knee: the highest offered rate at which a
window sustains no growing backlog.

    python -m bench.sweep --config qwen2-1.5b --traffic chat-steady \\
        --rates 8 12 16 20 --seconds 30 --seed 1

One process and one set-up serve every rate in turn, each on a fresh
server, with ``warmup_s`` of the traffic file before its window and no
drain.  A rate is sustained when the output tokens served in the window
reach ``1 - --tolerance`` of the output tokens the window's requests ask
for, per second.  (A count of requests in the system does not show the
backlog: ISRTF finishes the short requests and leaves the long ones.)
Prints one JSON line per rate, then one with the knee.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", default="chat-steady")
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--tolerance", type=float, default=0.05)
    args = ap.parse_args(argv)

    import jax

    from bench import harness, registry, traffic
    from bench.run import require_chips
    from repro.launch.compile_cache import enable_compile_cache

    require_chips(1)
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    dev = harness.device_info()
    cell = registry.Cell(name=f"{args.config}.sweep",
                         cfg=registry.config(args.config),
                         traffic=registry.traffic(args.traffic))
    tr = cell.traffic
    w0, w1, _ = harness.measured_window(tr, args.seconds)
    scheds = {r: traffic.schedule(tr, r, [w0, w1], args.seed)
              for r in args.rates}
    sys_ = harness.build(cell, args.seed)
    harness.warm_up(sys_, max(len(s) for s in scheds.values()))
    harness.log(f"setup_s={time.perf_counter() - T_PROCESS:.3f}")
    knee = None
    for r in sorted(args.rates):
        for jid in list(sys_.engine.slot_of):
            sys_.engine.evict_job(jid)
        sys_.server = harness.server(sys_)
        rec = harness.Record(cfg=cell.cfg, peaks=registry.peaks(dev["kind"]))
        reqs = scheds[r]
        harness.drive(sys_, reqs, w0, w1, 0.0, rec)

        arrived = sum(1 for d in rec.due.values() if w0 <= d < w1)
        offered = sum(q.max_tokens for q in reqs if w0 <= q.due < w1) \
            / args.seconds
        e2e = harness.end_to_end(rec)
        done = [x for x in rec.due if rec.in_window(x) and x in rec.finish]
        row = {"rate": r, "arrived": arrived, "finished": len(done),
               "offered_tokens_per_s": offered,
               "output_tokens_per_s": e2e["output_tokens_per_s"],
               "sustained": e2e["output_tokens_per_s"]
               >= (1 - args.tolerance) * offered,
               "jct_mean_s": e2e["jct_mean_s"],
               "ttft_p95_s": e2e["ttft_p95_s"],
               "windows": sum(1 for w in rec.windows
                              if w0 <= w["t0"] < w1),
               "batch_mean": registry.reader("batch_mean").read(rec),
               "engine_window_ms": registry.reader(
                   "engine_window_ms").read(rec)}
        print(json.dumps(row), flush=True)
        if row["sustained"]:
            knee = r
    print(json.dumps({"config": args.config, "knee_rps": knee,
                      "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Run one cell of the benchmark on the chips of this machine.

    python -m bench.run --workload qwen2-1.5b.chat-steady --seed 1 \\
        --seconds 30 --trace 0

Refuses any platform but a TPU, and fewer chips than the cell asks for,
with a non-zero exit and no result.  The last line of standard output is
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, with ``--trace 1`` also ``breakdown``, and last ``checks``:
each number compared with its limit, also printed as the last lines of
standard error.  With ``--trace 0`` the metrics are the cell's end-to-end
metrics, with ``--trace 1`` its per-layer metrics.
"""
from __future__ import annotations

import time

#: set-up is timed from the start of the process, imports included
T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))
#: traces and other run outputs (gitignored)
WORKDIR = ROOT / ".bench_out"


def require_chips(n: int) -> None:
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench.run needs a TPU; JAX found platform="
                         f"{devs[0].platform!r} ({len(devs)} device(s))")
    if len(devs) < n:
        raise SystemExit(f"the cell needs {n} chips, JAX found {len(devs)}")


def _finite(x):
    if isinstance(x, dict):
        return {k: _finite(v) for k, v in x.items()}
    if isinstance(x, list):
        return [_finite(v) for v in x]
    if isinstance(x, float) and not math.isfinite(x):
        return None
    return x


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness, registry

    cell = registry.cell(args.workload)
    require_chips(cell.chips)
    out = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                      T_PROCESS, WORKDIR)
    for name, c in out["checks"].items():
        print(f"[bench] check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(_finite(out)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

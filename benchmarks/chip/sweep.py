"""Find the serving knee once: offered rate against completed rate.

    python3 benchmarks/chip/sweep.py --workload serve.corpus_open \
        --seed 7 --seconds 8 --rates 10,20,30,40

Sets the cell up once (its ``rate`` only sizes the prepared requests:
give ``--horizon`` requests in all), then offers each rate in turn for
``--seconds`` on fresh requests and prints one JSON line per rate: completed
requests per second, p50/p95 latency from the due time, sheds, errors and
the generator's lateness.  The knee is the highest rate whose completions
keep pace and that sheds nothing; a cell's rate is set below it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(BENCH))
    import run_cell  # noqa: PLC0415
    sys.path.insert(0, str(run_cell.ROOT / "src"))
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(run_cell.COMPILE_CACHE)
    from harness import openloop  # noqa: PLC0415
    from harness.compile_meter import CompileMeter  # noqa: PLC0415
    rates = [float(r) for r in args.rates.split(",")]
    try:
        run_cell.device_info(1)
    except run_cell.CellError as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 1
    cell = run_cell.Cell(args.workload, args.seed)
    cell.params["rate"] = max(rates)
    cell.params["horizon_s"] = args.seconds * len(rates) + 1
    run_cell.enable_compile_cache(CompileMeter)
    st = cell.driver.setup(cell)
    start = 0
    try:
        for rate in rates:
            n = int(rate * args.seconds)
            slots = range(start, start + n)
            start += n

            def send(k, client, slots=slots):
                ok, shed, _ = client.send_prepared(
                    st["prepared"][slots[k]], decode=False)
                return ok, shed

            res = openloop.run(st["clients"], n, rate, send)
            lat = sorted(1e3 * v for v in res["latencies_s"])
            print(json.dumps({
                "offered_rps": rate, "requests": n,
                "completed_rps": len(lat) / res["wall_s"],
                "p50_ms": openloop.percentile(lat, 0.5),
                "p95_ms": openloop.percentile(lat, 0.95),
                "shed": res["shed"], "errors": res["errors"],
                "lateness_ms": res["lateness_ms"],
                "wall_s": res["wall_s"]}), flush=True)
    finally:
        cell.driver.release(st)
    return 0


if __name__ == "__main__":
    sys.exit(main())

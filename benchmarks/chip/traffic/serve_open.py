"""Open-loop serving: ``predict_batch`` requests at a fixed rate.

Set-up writes the configuration's three models (``reference/models.py``)
to ``.bench/models/`` in the checkout, starts the program's server
(``start_server``, in this process) and draws ``pool`` distinct blocks per
uarch from the seed.  Each request carries ``blocks`` blocks of one uarch,
the uarch drawn uniformly and the blocks uniformly from its pool; every
request is encoded for the binary wire in set-up.  Set-up warms the
predictor kernel's shapes (32, 48 and 64 uncached rows) on every uarch with
blocks outside the pools.

The window offers ``rate`` requests per second on a fixed schedule from
``conns`` client connections and times each request from when it was due
(the arithmetic of the program's ``service/loadgen.py``, copied in
``harness/openloop.py``).  Requests due in the window are all sent; the
window closes when the last has answered.  ``serve_p95_ms`` is the 95th
percentile of all of them, with a shed or failed request counted as
``FAILED_MS`` and in ``failed``.

``check`` compares every prediction of ``sample`` requests drawn from the
seed with the plain reference predictor, exactly (a request that never
answered counts as all its blocks wrong); ``device_path_faults`` counts a
predictor that is not on the chip's kernel, made no device call, or fell
back.
"""
from __future__ import annotations

import random
import shutil

from harness import blocks as gen
from harness import openloop
from reference import models, oracle
from reference import predictor as refpred

FAILED_MS = 30_000.0    # a shed or failed request: beyond any limit
TIMEOUT_S = 30.0


def setup(cell) -> dict:
    from repro.service.client import ServiceClient  # noqa: PLC0415
    from repro.service.server import start_server  # noqa: PLC0415
    p = cell.params
    isa, uarches = oracle.load_tables()
    names = list(cell.config["uarches"])
    models_dir = cell.root / ".bench" / "models"
    shutil.rmtree(models_dir, ignore_errors=True)
    models_dir.mkdir(parents=True)
    for n in names:
        (models_dir / f"{n}.xml").write_text(models.model_xml(isa, uarches[n]))
    rate, seconds_hint = float(p["rate"]), float(p.get("horizon_s", 60))
    per_req = int(p["blocks"])
    with cell.spans.span("bench.inputs"):
        pools = {n: gen.generate(isa, uarches[n], int(p["pool"]), cell.seed,
                                 min_len=cell.config["min_len"],
                                 max_len=cell.config["max_len"],
                                 tag="serve") for n in names}
        rng = random.Random(f"bench-serve/{cell.seed}")
        n_req = int(rate * seconds_hint) + 1
        plan = []
        for _ in range(n_req):
            u = rng.choice(names)
            plan.append((u, rng.sample(range(len(pools[u])), per_req)))
    srv = start_server(models_dir)
    clients = [ServiceClient(srv.host, srv.port, wire="binary",
                             timeout=TIMEOUT_S)
               for _ in range(int(p["conns"]))]
    with cell.spans.span("bench.encode"):
        prepared = [clients[0].prepare_batch(
            u, [gen.text(pools[u][i]) for i in idx]) for u, idx in plan]
    with cell.spans.span("bench.warmup"):
        for n in names:
            extra = gen.generate(isa, uarches[n], 400, cell.seed,
                                 min_len=cell.config["min_len"],
                                 max_len=cell.config["max_len"],
                                 tag="serve-warmup")
            texts = list(dict.fromkeys(gen.text(b) for b in extra))
            # 32, 40 and 60 uncached rows: the kernel's 32, 48, 64 buckets
            for lo, hi in ((0, 32), (32, 72), (72, 132)):
                envs = clients[0].predict_batch(n, texts[lo:hi])
                if not all(e.get("ok") for e in envs):
                    raise RuntimeError(f"warm-up request on {n} failed")
    stats0 = clients[0].stats()
    return {"srv": srv, "clients": clients, "plan": plan, "pools": pools,
            "prepared": prepared, "rate": rate, "stats0": stats0}


def window(st: dict, cell, seconds: float) -> dict:
    n_due = min(len(st["prepared"]), int(st["rate"] * seconds))
    if n_due < int(st["rate"] * seconds):
        raise ValueError("the cell's horizon_s is shorter than the window")
    st["sample"] = set(random.Random(f"bench-serve-sample/{cell.seed}")
                       .sample(range(n_due), int(cell.params["sample"])))
    kept: dict = {}

    def send(slot: int, client):
        want = slot in st["sample"]
        with cell.spans.span("bench.request"):
            ok, shed, envs = client.send_prepared(st["prepared"][slot],
                                                  decode=want)
        if want:
            kept[slot] = envs if ok else None
        return ok, shed

    with cell.traced():
        res = openloop.run(st["clients"], n_due, st["rate"], send,
                           timeout_s=TIMEOUT_S)
    st["kept"] = kept
    st["n_due"] = n_due
    lats_ms = [1e3 * v for v in res["latencies_s"]]
    failed = res["shed"] + res["errors"]
    lats_ms += [FAILED_MS] * failed
    stats = st["clients"][0].stats()
    st["stats1"] = stats
    ep = stats["endpoints"].get("predict_batch", {})
    c0, c1 = st["stats0"]["cache"], stats["cache"]
    hits, misses = c1["hits"] - c0["hits"], c1["misses"] - c0["misses"]
    return {"e2e": {"serve_p95_ms": openloop.percentile(lats_ms, 0.95)},
            "attempted": n_due, "failed": failed,
            "counters": {
                "endpoint_block_p50_us": ep.get("p50_us"),
                "blocks_per_request": len(st["plan"][0][1]),
                "cache_hits": hits, "cache_misses": misses,
                "predictor_before": st["stats0"].get("predictor", {}),
                "predictor_after": stats.get("predictor", {})},
            "notes": {"requests": n_due, "shed": res["shed"],
                      "errors": res["errors"],
                      "p50_ms": openloop.percentile(lats_ms, 0.5),
                      "max_ms": max(lats_ms) if lats_ms else None,
                      "lateness_ms": res["lateness_ms"],
                      "window_s": res["wall_s"]}}


def layer_inputs(st: dict, cell) -> dict:
    """The predictor kernel's problem: the device blocks and calls of the
    window, with each model's port combinations and min-cut candidates
    counted from the configuration's tables."""
    isa, uarches = oracle.load_tables()
    shapes = {}
    for n in cell.config["uarches"]:
        combos = {frozenset(pc) for rec in models.model_data(
            isa, uarches[n]).values() for pc in rec["usage"]}
        closure: set = set()
        for pc in combos:
            closure |= {pc} | {pc | s for s in closure}
        shapes[n] = (len(combos), len(closure))
    return {"model_shapes": shapes}


def release(st: dict) -> None:
    for c in st["clients"]:
        c.close()
    st["srv"].close()
    st["srv"] = None


def check(st: dict, cell) -> list:
    isa, uarches = oracle.load_tables()
    data = {n: models.model_data(isa, uarches[n])
            for n in cell.config["uarches"]}
    bad = checked = 0
    for slot in sorted(s for s in st["sample"] if s < st["n_due"]):
        u, idx = st["plan"][slot]
        envs = st["kept"].get(slot)
        if envs is None or len(envs) != len(idx):
            bad += len(idx)
            continue
        for i, env in zip(idx, envs):
            checked += 1
            want = refpred.predict(data[u], isa, st["pools"][u][i])
            got = env.get("result") if env.get("ok") else None
            if got is None or {k: got.get(k) for k in want} != want:
                bad += 1
    pred = st["stats1"].get("predictor", {})
    faults = 0
    if pred.get("backend") != "jax" or pred.get("device_waves", 0) == 0:
        faults += 1
    faults += pred.get("device_fallbacks", 0)
    st["check_notes"] = {"predictions_checked": checked}
    return [("served_mismatch", bad, 0), ("device_path_faults", faults, 0)]

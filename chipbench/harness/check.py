"""Whether what the timed path served is right: the served graph, ranks,
walk index and query answers against the plain reference.

Each number compared is reported with its limit.  Exact comparisons
(graph, walk structure, answers against the snapshot they name) have the
limit 0; the others take theirs from the configuration's ``limits``,
which PERF.md derives from measured readings.
"""
from __future__ import annotations

import concurrent.futures
import gc
import time

import numpy as np

from harness import reference as ref

PPR_SAMPLE = 4      # PPR answers compared with the exact PPR per run


def collect(run) -> dict:
    """Host copies of what the window served, taken before the program's
    state is freed: the final graph, the ranks of every generation a
    check names, the final walk array (left on the device) and the
    answers."""
    pubs = [p for p in run.publishes if p["window"]]
    final = pubs[-1]
    snap = run.store.snapshot()
    if snap.generation != final["gen"]:
        raise RuntimeError(f"published generation {snap.generation} after "
                           f"the window's last, {final['gen']}")
    g = snap.graph
    valid = np.asarray(g.valid)
    served_keys = ref.edge_keys(np.asarray(g.src)[valid],
                                np.asarray(g.dst)[valid], run.n)
    rng = np.random.default_rng([run.seed, 1])
    checked = [final]
    if len(pubs) > 1:
        checked.append(pubs[int(rng.integers(0, len(pubs) - 1))])
    gens = {p["gen"] for p in checked}
    gens |= {q["gen"] for q in run.queries if q.get("done") is not None}
    ranks = {gen: np.asarray(run.ranks_by_gen[gen]) for gen in gens}
    index = snap.ppr_index
    return dict(final=final, checked=checked, served_keys=served_keys,
                ranks=ranks, steps=None if index is None else index.steps,
                rng=rng)


def release(run) -> None:
    """Drop the program's state so the reference has the device."""
    run.engine = run.store = run.client = run.ingest = None
    run.ranks_by_gen = {}
    gc.collect()


def compare(run, out: dict, limits: dict, log, control: bool = False
            ) -> dict:
    """{name: {"value", "limit"}} for every number compared."""
    n, alpha = run.n, run.served["alpha"]
    t = time.perf_counter()
    acked = np.asarray(run.acked, np.int64)
    feed = run.data["feed"][acked]
    ev_keys = ref.edge_keys(feed[:, 0], feed[:, 1], n)
    ev_insert = run.data["insert"][acked]
    preload = ref.unique_keys(run.data["preload"], n)
    final = out["final"]
    graph = ref.GraphAt(n, preload, ev_keys, ev_insert, final["last_seq"] + 1)
    checks = {}

    served = np.sort(out["served_keys"])
    dup = int(len(served) - len(np.unique(served)))
    diff = dup + len(np.setdiff1d(served, graph.final_keys)) + \
        len(np.setdiff1d(graph.final_keys, served))
    checks["graph_diff"] = dict(value=diff, limit=0)

    l1 = []
    base = None
    for p in out["checked"]:
        a = graph.matrix(p["last_seq"] + 1)
        r = ref.pagerank(a, alpha, x0=base)
        base = r if base is None else base
        l1.append(float(np.abs(out["ranks"][p["gen"]] - r).sum()))
        log(f"check: generation {p['gen']} last_seq {p['last_seq']} "
            f"L1 {l1[-1]:.6e}")
    checks["rank_l1"] = dict(value=max(l1), limit=limits["rank_l1"])
    if control:
        low = ref.pagerank_lower_precision(graph.final_keys, n, alpha)
        log(f"control: float32 reference L1 "
            f"{float(np.abs(low - base).sum()):.6e}")

    answered = [q for q in run.queries if q.get("done") is not None]
    wrong = 0
    for q in answered:
        r = out["ranks"][q["gen"]]
        if q["kind"] == "point":
            wrong += not np.array_equal(r[np.asarray(q["arg"])], q["values"])
        elif q["kind"] == "top":
            best = np.sort(r)[::-1][:len(q["values"])]
            wrong += not (np.array_equal(best, q["values"]) and
                          np.array_equal(r[q["vertices"]], q["values"]))
    if answered:
        checks["query_diff"] = dict(value=wrong, limit=0)
    ppr_q = [q for q in answered if q["kind"] == "ppr"]
    if ppr_q:
        checks["ppr_gap"] = dict(
            value=_ppr_gap(ppr_q, graph, run.publishes, out["rng"], alpha,
                           control, log),
            limit=limits["ppr_gap"])

    if out["steps"] is not None:
        w = ref.walk_counts(out["steps"], graph.final_keys, preload, n)
        log(f"check: walks {w}")
        checks["walk_bad"] = dict(
            value=w["bad_source"] + w["bad_end"] + w["off_graph"], limit=0)
        if w["expected_new"] >= limits["walk_min_expected"]:
            checks["walk_new_deficit"] = dict(
                value=max(0.0, 1.0 - w["on_new"] / w["expected_new"]),
                limit=limits["walk_new_deficit"])
    log(f"check: reference took {time.perf_counter() - t:.3f}s")
    return checks


def _ppr_gap(queries: list, graph, publishes: list, rng, alpha: float,
             control: bool, log) -> float:
    """Largest shortfall of PPR mass on the answered top k against the
    exact top k, over a sample of the PPR answers drawn from the seed,
    answers from seeds with out-edges first (a seed without any has an
    exact answer, itself)."""
    deg = np.bincount(graph.final_keys // graph.n, minlength=graph.n)
    order = rng.permutation(len(queries))
    live = [i for i in order if deg[queries[i]["arg"][0]] > 0]
    rest = [i for i in order if deg[queries[i]["arg"][0]] == 0]
    sample = [queries[i] for i in (live + rest)[:PPR_SAMPLE]]
    last_seq = {p["gen"]: p["last_seq"] for p in publishes}
    matrices = {q["gen"]: graph.matrix(last_seq[q["gen"]] + 1)
                for q in sample}

    def exact(q):
        return ref.personalized(matrices[q["gen"]],
                                np.asarray(q["arg"][:1]), alpha)[:, 0]

    with concurrent.futures.ThreadPoolExecutor(len(sample)) as pool:
        pis = list(pool.map(exact, sample))
    gaps = [ref.top_mass_gap(pi, q["vertices"], len(q["vertices"]))
            for pi, q in zip(pis, sample)]
    log(f"check: ppr gaps {[round(g, 6) for g in gaps]}")
    if control:
        altered = [ref.top_mass_gap(pi, rng.integers(0, graph.n, size=k), k)
                   for pi, k in ((pi, len(q["vertices"]))
                                 for pi, q in zip(pis, sample))]
        log(f"control: ppr gaps of answers of random vertices "
            f"{[round(g, 6) for g in altered]}")
    return max(gaps)

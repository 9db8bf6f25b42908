"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this produces:
  * ``compiled.memory_analysis()``  — proves the cell fits per-device HBM;
  * ``compiled.cost_analysis()``    — HLO FLOPs / bytes for §Roofline;
  * collective-bytes tally parsed from the optimised HLO text
    (all-gather / all-reduce / reduce-scatter / all-to-all /
    collective-permute) — cost_analysis does not report these.

Results stream to ``results/dryrun_<mesh>.json`` which
benchmarks/roofline consumes.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --mesh single --arch all
  PYTHONPATH=src python -m repro.launch.dryrun --mesh multi  --arch gemma3-12b --shape train_4k

Run as a script it forces 512 host devices (``REPRO_DRYRUN_DEVICES``
overrides the count) by appending to ``XLA_FLAGS`` before JAX starts;
importing it, as the tests do, leaves ``XLA_FLAGS`` alone.
"""
import os

if __name__ == "__main__":
    os.environ["XLA_FLAGS"] = " ".join(filter(None, [
        os.environ.get("XLA_FLAGS"),
        "--xla_force_host_platform_device_count="
        + os.environ.get("REPRO_DRYRUN_DEVICES", "512")]))

import argparse
import dataclasses
import json
import re
import sys
import time
from functools import partial

import jax
import jax.numpy as jnp

import repro  # noqa: F401
from repro.configs.registry import REGISTRY, all_cells, get_arch
from repro.dist import sharding as SH
from repro.dist.pagerank_dist import (build_distributed_step,
                                      distributed_in_shardings,
                                      distributed_input_specs)
from repro.launch.mesh import auto_mesh, data_axes, make_production_mesh
from repro.train import inputs as I
from repro.train import steps as S

_OP_RE = re.compile(
    r"=\s+(\(?[a-z0-9\[\],{}\s]*?\)?)\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?\(")
_SHAPE_RE = re.compile(r"(bf16|f16|f32|f64|s32|u32|s8|u8|pred|s64|u64)"
                       r"\[([\d,]*)\]")
_BYTES = dict(bf16=2, f16=2, f32=4, f64=8, s32=4, u32=4, s8=1, u8=1,
              pred=1, s64=8, u64=8)


def collective_bytes(hlo_text: str) -> dict:
    """Sum result bytes of every collective op in optimised HLO.

    NOTE: ops inside while/scan bodies are counted ONCE (XLA text has one
    body per loop).  The roofline layer (roofline/analysis.py) therefore
    consumes counts from the *counting-mode* lowering, where layer loops
    are unrolled — see EXPERIMENTS.md §Method.
    """
    out: dict = {k: 0 for k in ("all-gather", "all-reduce", "reduce-scatter",
                                "all-to-all", "collective-permute")}
    counts: dict = {k: 0 for k in out}
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        if m.group(3) == "-done":      # start/done pairs: count starts only
            continue
        kind = m.group(2)
        nbytes = 0
        for dt, dims in _SHAPE_RE.findall(m.group(1)):
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            nbytes += n * _BYTES[dt]
        out[kind] += nbytes
        counts[kind] += 1
    out["total"] = sum(out.values())
    out["op_counts"] = counts
    return out


def _mem_dict(compiled) -> dict:
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return {}
    if ma is None:
        return {}
    keys = ("argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "alias_size_in_bytes",
            "generated_code_size_in_bytes")
    d = {}
    for k in keys:
        v = getattr(ma, k, None)
        if v is not None:
            d[k] = int(v)
    d["peak_per_device_bytes"] = (
        d.get("argument_size_in_bytes", 0) + d.get("output_size_in_bytes", 0)
        + d.get("temp_size_in_bytes", 0) - d.get("alias_size_in_bytes", 0))
    return d


def _cost_dict(compiled) -> dict:
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return {}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return {k: float(v) for k, v in dict(ca).items()
            if isinstance(v, (int, float)) and (
                "flops" in k or "bytes" in k or k in ("utilization",))}


# ---------------------------------------------------------------------------
# cell lowering
# ---------------------------------------------------------------------------

def lower_cell(spec, cell, mesh, counting: bool = False,
               n_layers: int | None = None):
    """Lower one (arch × shape) on a mesh.

    counting=True (LM family): unrolled layers + chunk=seq so XLA's
    count-bodies-once cost analysis and the collective parser see the whole
    program.  With ``n_layers`` override, the L=1/L=2 delta trick
    extrapolates exact full-depth costs (layer stacks are homogeneous —
    gemma3's local/global layers share one HLO since the window is a
    traced scalar).  The production (scan+remat) variant proves memory.
    """
    family = spec.family
    if family == "pagerank":
        d = cell.dims
        fn = build_distributed_step(mesh, n_vertices=d["n_vertices"])
        args = distributed_input_specs(mesh, d["n_vertices"],
                                       d["edge_capacity"])
        shardings = distributed_in_shardings(mesh)
        return jax.jit(fn, in_shardings=shardings).lower(*args)

    cfg = I.effective_config(spec, cell, smoke=False)
    if counting and family == "lm":
        cfg = dataclasses.replace(cfg, counting=True)
    if n_layers is not None and family == "lm":
        cfg = dataclasses.replace(cfg, n_layers=n_layers)
    spec = dataclasses.replace(spec, config=cfg)
    batch = I.build_inputs(spec, cell, concrete=False, smoke=False)

    if family == "lm":
        if cell.kind == "train":
            params, opt = I.abstract_state(spec, cell)
            pspec, bspec, ospec = SH.family_shardings(
                "lm", mesh, params, batch, opt)
            # production variant: microbatched accumulation; counting
            # variant: single batch (FLOP-identical, scan-free)
            import jax.numpy as _jnp
            n_micro = 1 if counting else I.MICROBATCHES.get(spec.arch_id, 1)
            fn = S.make_lm_train_step(
                cfg, n_microbatches=n_micro,
                factored=I.FACTORED_V.get(spec.arch_id, False),
                accum_dtype=I.ACCUM_DTYPE.get(spec.arch_id, _jnp.float32))
            return jax.jit(fn, in_shardings=(pspec, ospec, bspec),
                           out_shardings=(pspec, ospec, None),
                           donate_argnums=(0, 1)).lower(params, opt, batch)
        if cell.kind == "prefill":
            params, _ = I.abstract_state(spec, cell, with_opt=False)
            pspec, bspec, _ = SH.family_shardings("lm", mesh, params, batch)
            fn = S.make_lm_prefill(cfg)
            return jax.jit(fn, in_shardings=(pspec, bspec["tokens"]),
                           ).lower(params, batch["tokens"])
        # decode
        params, _ = I.abstract_state(spec, cell, with_opt=False)
        cache = I.abstract_cache(spec, cell)
        pspec, _, _ = SH.family_shardings(
            "lm", mesh, params, dict(tokens=batch["tokens"]))
        cspec = SH.lm_cache_specs(mesh, cache, cell.dims["batch"])
        dp = data_axes(mesh)
        from jax.sharding import NamedSharding, PartitionSpec as P
        tok_spec = NamedSharding(
            mesh, P(dp if cell.dims["batch"] % max(
                1, SH._axis_size(mesh, dp)) == 0 else None, None))
        fn = S.make_lm_decode_step(cfg)
        return jax.jit(fn, in_shardings=(pspec, cspec, tok_spec),
                       out_shardings=(None, cspec),
                       donate_argnums=(1,)).lower(
            params, cache, batch["tokens"])

    if family == "gnn":
        params, opt = I.abstract_state(spec, cell)
        pspec, bspec, ospec = SH.family_shardings(
            "gnn", mesh, params, batch, opt)
        fn = S.make_gnn_train_step(spec.arch_id, cfg)
        return jax.jit(fn, in_shardings=(pspec, ospec, bspec),
                       out_shardings=(pspec, ospec, None),
                       donate_argnums=(0, 1)).lower(params, opt, batch)

    # recsys
    if cell.kind == "recsys_train":
        params, opt = I.abstract_state(spec, cell)
        pspec, bspec, ospec = SH.family_shardings(
            "recsys", mesh, params, batch, opt)
        fn = S.make_recsys_train_step(cfg)
        return jax.jit(fn, in_shardings=(pspec, ospec, bspec),
                       out_shardings=(pspec, ospec, None),
                       donate_argnums=(0, 1)).lower(params, opt, batch)
    params, _ = I.abstract_state(spec, cell, with_opt=False)
    pspec, bspec, _ = SH.family_shardings("recsys", mesh, params, batch)
    fn = S.make_recsys_serve(cfg) if cell.kind == "recsys_serve" \
        else S.make_recsys_retrieval(cfg)
    return jax.jit(fn, in_shardings=(pspec, bspec)).lower(params, batch)


def run_cell(spec, cell, mesh, mesh_name: str, verbose=True) -> dict:
    rec = dict(arch=spec.arch_id, shape=cell.name, mesh=mesh_name,
               family=spec.family, kind=cell.kind)
    if cell.skip:
        rec["status"] = "SKIP"
        rec["skip_reason"] = cell.skip
        return rec
    t0 = time.time()
    try:
        with jax.set_mesh(mesh):
            lowered = lower_cell(spec, cell, mesh)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            rec["memory"] = _mem_dict(compiled)
            rec["cost"] = _cost_dict(compiled)
            try:
                hlo = compiled.as_text()
            except Exception:
                hlo = lowered.as_text()
            rec["collectives"] = collective_bytes(hlo)
            rec["hlo_bytes"] = len(hlo)
            # counting-mode lowerings for exact roofline terms: L=1 and
            # L=2 unrolled, extrapolated to full depth (delta trick)
            if spec.family == "lm":
                t1 = time.time()
                c1 = lower_cell(spec, cell, mesh, counting=True,
                                n_layers=1).compile()
                c2 = lower_cell(spec, cell, mesh, counting=True,
                                n_layers=2).compile()
                L = spec.config.n_layers
                cost1, cost2 = _cost_dict(c1), _cost_dict(c2)
                coll1 = collective_bytes(c1.as_text())
                coll2 = collective_bytes(c2.as_text())

                def extrap(a, b):
                    return {k: a.get(k, 0) + (L - 1) *
                            (b.get(k, 0) - a.get(k, 0))
                            for k in set(a) | set(b)
                            if not isinstance(a.get(k, b.get(k)), dict)}

                rec["cost_counting"] = {
                    k: v for k, v in extrap(cost1, cost2).items()
                    if k in ("flops", "bytes accessed")}
                rec["collectives_counting"] = extrap(coll1, coll2)
                rec["counting_method"] = f"delta L=1/2 -> L={L}"
                rec["t_counting_s"] = round(time.time() - t1, 1)
        rec["status"] = "OK"
        rec["t_lower_s"] = round(t_lower, 1)
        rec["t_compile_s"] = round(t_compile, 1)
        if verbose:
            mem = rec["memory"].get("peak_per_device_bytes", 0)
            fl = rec["cost"].get("flops", 0)
            cb = rec["collectives"]["total"]
            print(f"  OK {spec.arch_id}/{cell.name}: "
                  f"peak/dev={mem/2**30:.2f}GiB flops={fl:.3g} "
                  f"coll={cb/2**20:.1f}MiB "
                  f"(lower {t_lower:.0f}s compile {t_compile:.0f}s)",
                  flush=True)
    except Exception as e:  # noqa: BLE001 — report, don't abort the sweep
        rec["status"] = "FAIL"
        rec["error"] = repr(e)[:500]
        if verbose:
            print(f"  FAIL {spec.arch_id}/{cell.name}: {repr(e)[:200]}",
                  flush=True)
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--include-pagerank", action="store_true")
    ap.add_argument("--out", default="results")
    args = ap.parse_args(argv)

    os.makedirs(args.out, exist_ok=True)
    meshes = {"single": False, "multi": True}
    wanted = [args.mesh] if args.mesh != "both" else ["single", "multi"]

    def build_mesh(multi_pod: bool):
        ndev = len(jax.devices())
        if ndev >= (512 if multi_pod else 256):
            return make_production_mesh(multi_pod=multi_pod)
        # CI-scale override (REPRO_DRYRUN_DEVICES): shrink proportionally
        if multi_pod:
            d = ndev // 4
            return auto_mesh((2, d, 2), ("pod", "data", "model"))
        return auto_mesh((ndev // 2, 2), ("data", "model"))

    for mesh_name in wanted:
        mesh = build_mesh(meshes[mesh_name])
        print(f"=== mesh {mesh_name}: {dict(mesh.shape)} "
              f"({len(jax.devices())} devices) ===", flush=True)
        records = []
        path = os.path.join(args.out, f"dryrun_{mesh_name}.json")
        # resume support: skip cells already recorded OK
        done = {}
        if os.path.exists(path):
            with open(path) as f:
                for r in json.load(f):
                    if r.get("status") in ("OK", "SKIP"):
                        done[(r["arch"], r["shape"])] = r
        for spec, cell in all_cells(include_pagerank=args.include_pagerank):
            if args.arch != "all" and spec.arch_id != args.arch:
                continue
            if args.shape != "all" and cell.name != args.shape:
                continue
            if (spec.arch_id, cell.name) in done:
                records.append(done[(spec.arch_id, cell.name)])
                print(f"  cached {spec.arch_id}/{cell.name}", flush=True)
                continue
            records.append(run_cell(spec, cell, mesh, mesh_name))
            with open(path, "w") as f:
                json.dump(records, f, indent=1)
        ok = sum(r["status"] == "OK" for r in records)
        sk = sum(r["status"] == "SKIP" for r in records)
        fail = [r for r in records if r["status"] == "FAIL"]
        print(f"mesh {mesh_name}: {ok} OK, {sk} SKIP, {len(fail)} FAIL")
        for r in fail:
            print(f"  FAILED {r['arch']}/{r['shape']}: {r['error'][:120]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())

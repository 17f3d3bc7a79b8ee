import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# --- everything below may import jax -----------------------------------------
import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import ARCHS, get_config  # noqa: E402
from repro.launch import analysis  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.shapes import (  # noqa: E402
    CELLS,
    cell_applicable,
    input_specs,
    opt_specs,
    params_specs,
)
from repro.models import decode_step, prefill  # noqa: E402
from repro.parallel.sharding import (  # noqa: E402
    batch_shardings,
    cache_shardings,
    param_shardings,
    replicated,
)
from repro.train import TrainConfig, make_train_step  # noqa: E402

"""Multi-pod dry-run: .lower().compile() every (arch x shape x mesh) cell on
the 16x16 (single-pod) and 2x16x16 (multi-pod) production meshes, print
memory/cost analysis, and dump the roofline inputs to JSON.

This is the proof of distribution coherence without hardware: a sharding
mismatch, compile-time OOM, or unsupported collective fails the cell.
"""


def _devices_sliced(multi_pod: bool):
    n = 512 if multi_pod else 256
    return np.array(jax.devices()[:n])


def make_mesh(multi_pod: bool):
    # jax.make_mesh uses all devices; build explicitly on the slice we need
    from jax.sharding import Mesh

    devs = _devices_sliced(multi_pod)
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return Mesh(devs.reshape(shape), axes)


def _parse_variant(variant: str) -> dict:
    """"zero1,remat" -> {zero1: True, ...}; "n_heads=64" -> {n_heads: 64}."""
    out = {}
    for tok in variant.split(","):
        tok = tok.strip()
        if not tok:
            continue
        if "=" in tok:
            k, v = tok.split("=", 1)
            out[k.strip()] = int(v)
        else:
            out[tok] = True
    return out


def lower_cell(arch: str, cell_name: str, multi_pod: bool, *, unroll: bool = False,
               variant: str = ""):
    """Lower + compile one cell; returns the analysis record.

    ``unroll=True`` lowers with layers/loss-chunks unrolled: XLA's
    cost_analysis counts while-loop bodies ONCE (verified empirically), so
    scanned modules under-report flops/bytes by ~n_layers.  The roofline
    table therefore uses unrolled lowering; the scan variant remains the
    deploy/compile-check path.

    ``variant``: comma-separated ModelConfig boolean overrides (e.g.
    "pure_dp", "remat", "pure_dp,remat") — the §Perf hillclimb knobs.
    """
    import dataclasses as _dc

    cfg = get_config(arch)
    if unroll:
        cfg = _dc.replace(cfg, scan_layers=False, scan_loss=False)
    if variant:
        cfg = _dc.replace(cfg, **_parse_variant(variant))
    cell = CELLS[cell_name]
    ok, reason = cell_applicable(cfg, cell)
    if not ok:
        return {"arch": arch, "cell": cell_name, "multi_pod": multi_pod,
                "status": "skipped", "reason": reason}

    mesh = make_mesh(multi_pod)
    n_chips = int(np.prod(list(mesh.shape.values())))
    p_sds = params_specs(cfg)
    p_sh = param_shardings(p_sds, cfg, mesh)
    spec = input_specs(cfg, cell)

    t0 = time.perf_counter()
    if cell.kind == "train":
        tcfg = TrainConfig()
        step_fn = make_train_step(cfg, tcfg)
        o_sds = opt_specs(p_sds)
        o_sh = param_shardings(o_sds, cfg, mesh, role="opt")
        b_sh = batch_shardings(spec, cfg, mesh)
        fn = jax.jit(
            step_fn,
            in_shardings=(p_sh, o_sh, replicated(mesh), b_sh),
            out_shardings=(p_sh, o_sh, None),
            donate_argnums=(0, 1),
        )
        with mesh:
            lowered = fn.lower(
                p_sds, o_sds, jax.ShapeDtypeStruct((), jnp.int32), spec
            )
    elif cell.kind == "prefill":
        cache_sds = spec["caches"]
        c_sh = cache_shardings(cache_sds, cfg, mesh)
        tok_sh = batch_shardings({"tokens": spec["tokens"]}, cfg, mesh)["tokens"]
        args = [spec["tokens"], cache_sds]
        in_sh = [p_sh, tok_sh, c_sh]
        if "prefix_embeds" in spec:
            pe_sh = batch_shardings(
                {"prefix_embeds": spec["prefix_embeds"]}, cfg, mesh
            )["prefix_embeds"]

            def prefill_fn(params, tokens, caches, prefix_embeds):
                return prefill(params, cfg, tokens, caches,
                               prefix_embeds=prefix_embeds)

            args.append(spec["prefix_embeds"])
            in_sh.append(pe_sh)
        else:

            def prefill_fn(params, tokens, caches):
                return prefill(params, cfg, tokens, caches)

        fn = jax.jit(prefill_fn, in_shardings=tuple(in_sh),
                     donate_argnums=(2,))
        with mesh:
            lowered = fn.lower(p_sds, *args)
    else:  # decode
        cache_sds = spec["caches"]
        c_sh = cache_shardings(cache_sds, cfg, mesh)
        tok_sh = batch_shardings({"token": spec["token"]}, cfg, mesh)["token"]

        def decode_fn(params, token, pos, caches):
            return decode_step(params, cfg, token, pos, caches)

        fn = jax.jit(
            decode_fn,
            in_shardings=(p_sh, tok_sh, replicated(mesh), c_sh),
            out_shardings=(None, c_sh),
            donate_argnums=(3,),
        )
        with mesh:
            lowered = fn.lower(
                p_sds, spec["token"], jax.ShapeDtypeStruct((), jnp.int32),
                cache_sds
            )
    t_lower = time.perf_counter() - t0

    t0 = time.perf_counter()
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t0

    mem = None
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            mem = {
                k: int(getattr(ma, k))
                for k in (
                    "argument_size_in_bytes",
                    "output_size_in_bytes",
                    "temp_size_in_bytes",
                    "generated_code_size_in_bytes",
                )
                if hasattr(ma, k)
            }
    except Exception as e:  # CPU backend may not support it
        mem = {"error": str(e)}
    cost = {}
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        cost = {k: float(v) for k, v in dict(ca).items()
                if isinstance(v, (int, float))}
    except Exception as e:
        cost = {"error": str(e)}

    coll = analysis.collective_bytes(compiled.as_text())
    roof = analysis.roofline(cost, coll["total_bytes"], n_chips)
    mf = analysis.model_flops(cfg, cell)
    record = {
        "arch": arch,
        "cell": cell_name,
        "multi_pod": multi_pod,
        "status": "ok",
        "n_chips": n_chips,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": mem,
        "cost_flops": cost.get("flops"),
        "cost_bytes": cost.get("bytes accessed"),
        "collectives": coll,
        "roofline": roof,
        "model_flops": mf,
        "useful_ratio": (
            mf / roof["hlo_flops_global"] if roof["hlo_flops_global"] else None
        ),
    }
    return record


def probe_cell(arch: str, cell_name: str, multi_pod: bool, variant: str = ""):
    """Depth-probe roofline: lower the arch UNROLLED at 1 and 2 pattern
    periods, take the per-period marginal cost (embed/unembed/loss isolate in
    the diff), extrapolate to the real depth.

    Rationale: full-depth unrolled compiles take 8-40 min per cell on this
    host (MoE worst); the probe needs two sub-minute compiles and is exact
    for homogeneous stacks (validated against full unrolls of the deepseek
    archs — see EXPERIMENTS.md §Roofline).
    """
    import dataclasses as _dc

    cfg0 = get_config(arch)
    cell = CELLS[cell_name]
    ok, reason = cell_applicable(cfg0, cell)
    if not ok:
        return {"arch": arch, "cell": cell_name, "multi_pod": multi_pod,
                "status": "skipped", "reason": reason}
    base = cfg0.first_k_dense
    period = cfg0.period

    def shallow(n_periods):
        cfg = _dc.replace(
            cfg0, n_layers=base + period * n_periods,
            scan_layers=False, scan_loss=False,
        )
        if variant:
            cfg = _dc.replace(cfg, **_parse_variant(variant))
        return cfg

    recs = []
    for np_ in (1, 2):
        recs.append(
            _lower_one(shallow(np_), cell, multi_pod, donate=False)
        )
    r1, r2 = recs
    n_periods_real = (cfg0.n_layers - base) / period
    out = {"arch": arch, "cell": cell_name, "multi_pod": multi_pod,
           "status": "ok", "method": "depth_probe",
           "n_chips": r1["n_chips"],
           "compile_s": r1["compile_s"] + r2["compile_s"]}
    if variant:
        out["variant"] = variant

    def extrap(a, b):
        if a is None or b is None:
            return None
        return a + (b - a) * (n_periods_real - 1)

    flops = extrap(r1["cost_flops"], r2["cost_flops"])
    bytes_ = extrap(r1["cost_bytes"], r2["cost_bytes"])
    coll = extrap(
        r1["collectives"]["total_bytes"], r2["collectives"]["total_bytes"]
    )
    out["cost_flops"] = flops
    out["cost_bytes"] = bytes_
    out["collectives"] = {
        "total_bytes": coll,
        "counts_1p": r1["collectives"]["counts"],
        "counts_2p": r2["collectives"]["counts"],
    }
    out["roofline"] = analysis.roofline(
        {"flops": flops, "bytes accessed": bytes_}, int(coll), r1["n_chips"]
    )
    mf = analysis.model_flops(cfg0, cell)
    out["model_flops"] = mf
    out["useful_ratio"] = (
        mf / out["roofline"]["hlo_flops_global"]
        if out["roofline"]["hlo_flops_global"] else None
    )
    return out


def _lower_one(cfg, cell, multi_pod: bool, donate: bool = True):
    """Shared lower+compile+analyze for a concrete config."""
    mesh = make_mesh(multi_pod)
    n_chips = int(np.prod(list(mesh.shape.values())))
    p_sds = params_specs(cfg)
    p_sh = param_shardings(p_sds, cfg, mesh)
    spec = input_specs(cfg, cell)
    t0 = time.perf_counter()
    if cell.kind == "train":
        tcfg = TrainConfig()
        step_fn = make_train_step(cfg, tcfg)
        o_sds = opt_specs(p_sds)
        o_sh = param_shardings(o_sds, cfg, mesh, role="opt")
        b_sh = batch_shardings(spec, cfg, mesh)
        fn = jax.jit(
            step_fn,
            in_shardings=(p_sh, o_sh, replicated(mesh), b_sh),
            out_shardings=(p_sh, o_sh, None),
        )
        with mesh:
            lowered = fn.lower(p_sds, o_sds, jax.ShapeDtypeStruct((), jnp.int32), spec)
    elif cell.kind == "prefill":
        cache_sds = spec["caches"]
        c_sh = cache_shardings(cache_sds, cfg, mesh)
        tok_sh = batch_shardings({"tokens": spec["tokens"]}, cfg, mesh)["tokens"]
        args = [spec["tokens"], cache_sds]
        in_sh = [p_sh, tok_sh, c_sh]
        if "prefix_embeds" in spec:
            pe_sh = batch_shardings(
                {"prefix_embeds": spec["prefix_embeds"]}, cfg, mesh
            )["prefix_embeds"]

            def prefill_fn(params, tokens, caches, prefix_embeds):
                return prefill(params, cfg, tokens, caches,
                               prefix_embeds=prefix_embeds)

            args.append(spec["prefix_embeds"])
            in_sh.append(pe_sh)
        else:

            def prefill_fn(params, tokens, caches):
                return prefill(params, cfg, tokens, caches)

        fn = jax.jit(prefill_fn, in_shardings=tuple(in_sh))
        with mesh:
            lowered = fn.lower(p_sds, *args)
    else:
        cache_sds = spec["caches"]
        c_sh = cache_shardings(cache_sds, cfg, mesh)
        tok_sh = batch_shardings({"token": spec["token"]}, cfg, mesh)["token"]

        def decode_fn(params, token, pos, caches):
            return decode_step(params, cfg, token, pos, caches)

        fn = jax.jit(
            decode_fn,
            in_shardings=(p_sh, tok_sh, replicated(mesh), c_sh),
            out_shardings=(None, c_sh),
        )
        with mesh:
            lowered = fn.lower(
                p_sds, spec["token"], jax.ShapeDtypeStruct((), jnp.int32), cache_sds
            )
    t_lower = time.perf_counter() - t0
    t0 = time.perf_counter()
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t0
    cost = {}
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        cost = {k: float(v) for k, v in dict(ca).items()
                if isinstance(v, (int, float))}
    except Exception as e:
        cost = {"error": str(e)}
    coll = analysis.collective_bytes(compiled.as_text())
    return {
        "n_chips": n_chips,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "cost_flops": cost.get("flops"),
        "cost_bytes": cost.get("bytes accessed"),
        "collectives": coll,
    }


def lower_trueknn_cell(multi_pod: bool, engine: str = "dense"):
    """The paper's own technique as a dry-run cell.

    engine="dense": one-pass streaming top-k over mesh-sharded points
    (hypercube merge) — the baseline.
    engine="grid":  one fixed-radius round over stacked per-shard hash grids
    (the paper's candidate pruning at scale) — the §Perf optimized variant.
    Grid shape stand-ins use the measured scaling of the hash grid on uniform
    data (table ~ 2·N_local, cap 16 at round-1 radii).
    """
    from repro.configs import TRUEKNN_CONFIG as kcfg
    from repro.core.distributed import make_distributed_knn
    from repro.core.distributed_grid import make_grid_round

    mesh = make_mesh(multi_pod)
    n_chips = int(np.prod(list(mesh.shape.values())))
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    p_size = mesh.shape["model"]
    t0 = time.perf_counter()
    if engine == "dense":
        # interpret-mode Pallas lowers to plain HLO on CPU; on TPU the same
        # call compiles the Mosaic kernel — either way it proves the sharding.
        fn = make_distributed_knn(mesh, kcfg.k, use_kernel=True)
        n_total = kcfg.n_points * p_size
        pts = jax.ShapeDtypeStruct((n_total, kcfg.dim), jnp.float32)
        qs = jax.ShapeDtypeStruct((kcfg.n_queries, kcfg.dim), jnp.float32)
        qid = jax.ShapeDtypeStruct((kcfg.n_queries,), jnp.int32)
        jfn = jax.jit(
            fn,
            in_shardings=(
                NamedSharding(mesh, P("model", None)),
                NamedSharding(mesh, P(batch_axes, None)),
                NamedSharding(mesh, P(batch_axes)),
            ),
        )
        with mesh:
            lowered = jfn.lower(pts, qs, qid)
    else:
        nl, d = kcfg.n_points, kcfg.dim
        table = 1 << 21  # ~2x load factor at 1M pts/shard
        cap = 16
        fn = make_grid_round(mesh, kcfg.k, table, nl, chunk=1024)
        gsh = NamedSharding(mesh, P("model"))
        args = (
            jax.ShapeDtypeStruct((p_size, table, cap), jnp.int32),
            (jax.ShapeDtypeStruct((p_size, table, cap), jnp.float32),) * d,
            jax.ShapeDtypeStruct((p_size, d), jnp.float32),
            jax.ShapeDtypeStruct((p_size, d), jnp.float32),
            jax.ShapeDtypeStruct((p_size, d), jnp.int32),
            jax.ShapeDtypeStruct((kcfg.n_queries, d), jnp.float32),
            jax.ShapeDtypeStruct((kcfg.n_queries,), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.float32),
        )
        jfn = jax.jit(
            fn,
            in_shardings=(
                gsh, gsh, gsh, gsh, gsh,
                NamedSharding(mesh, P(batch_axes, None)),
                NamedSharding(mesh, P(batch_axes)),
                NamedSharding(mesh, P()),
            ),
        )
        with mesh:
            lowered = jfn.lower(*args)
    t_lower = time.perf_counter() - t0
    t0 = time.perf_counter()
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t0
    cost = {}
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        cost = {k: float(v) for k, v in dict(ca).items()
                if isinstance(v, (int, float))}
    except Exception as e:
        cost = {"error": str(e)}
    coll = analysis.collective_bytes(compiled.as_text())
    roof = analysis.roofline(cost, coll["total_bytes"], n_chips)
    return {
        "arch": "trueknn",
        "engine": engine,
        "cell": f"knn_{engine}_{kcfg.n_points}x{mesh.shape['model']}pts_{kcfg.n_queries}q",
        "multi_pod": multi_pod,
        "status": "ok",
        "n_chips": n_chips,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "cost_flops": cost.get("flops"),
        "cost_bytes": cost.get("bytes accessed"),
        "collectives": coll,
        "roofline": roof,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--cell", default="all")
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="both")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--force", action="store_true")
    ap.add_argument(
        "--unroll", action="store_true",
        help="unroll layers/loss for truthful cost_analysis (roofline pass)",
    )
    ap.add_argument(
        "--variant", default="",
        help="comma-separated ModelConfig bool overrides (pure_dp, remat)",
    )
    ap.add_argument(
        "--probe", action="store_true",
        help="depth-probe roofline (unrolled 1 vs 2 periods, extrapolated)",
    )
    ap.add_argument(
        "--knn-engine", default="dense", choices=["dense", "grid"],
        help="trueknn cell engine (grid = per-shard hash grids, §Perf)",
    )
    args = ap.parse_args()

    archs = list(ARCHS) + ["trueknn"] if args.arch == "all" else [args.arch]
    cells = list(CELLS) if args.cell == "all" else [args.cell]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    os.makedirs(args.out, exist_ok=True)
    for arch in archs:
        for multi_pod in meshes:
            for cell in (["-"] if arch == "trueknn" else cells):
                tag = f"{arch}__{cell}__{'multi' if multi_pod else 'single'}"
                path = os.path.join(args.out, tag + ".json")
                if os.path.exists(path) and not args.force:
                    print(f"[skip existing] {tag}")
                    continue
                print(f"[lower+compile] {tag} ...", flush=True)
                try:
                    if arch == "trueknn":
                        rec = lower_trueknn_cell(multi_pod, engine=args.knn_engine)
                    elif args.probe:
                        rec = probe_cell(arch, cell, multi_pod, args.variant)
                    else:
                        rec = lower_cell(arch, cell, multi_pod, unroll=args.unroll,
                                         variant=args.variant)
                        if args.variant:
                            rec["variant"] = args.variant
                except Exception as e:
                    rec = {
                        "arch": arch, "cell": cell, "multi_pod": multi_pod,
                        "status": "error", "error": f"{type(e).__name__}: {e}",
                        "traceback": traceback.format_exc()[-4000:],
                    }
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
                status = rec["status"]
                extra = (
                    f" compile={rec.get('compile_s')}s dominant={rec['roofline']['dominant']}"
                    if status == "ok" and "roofline" in rec
                    else rec.get("reason", rec.get("error", ""))[:200]
                )
                print(f"  -> {status}{extra}", flush=True)


if __name__ == "__main__":
    main()

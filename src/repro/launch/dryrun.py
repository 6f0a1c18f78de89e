"""Multi-pod dry-run (assignment deliverable e).

For every (architecture x shape) cell and mesh, AOT-lower and compile the
real step program (train_step for train shapes, forward for prefill,
decode_step for decode shapes) against ShapeDtypeStruct inputs on the
production mesh, then record memory_analysis / cost_analysis / collective
bytes for §Dry-run and §Roofline. No arrays are ever allocated.

Run:  PYTHONPATH=src python -m repro.launch.dryrun [--arch A] [--shape S]
      [--multi-pod] [--all]
Results accumulate in benchmarks/dryrun_results.json (incremental cache).
Run as a script it forces 512 host devices (``REPRO_DRYRUN_XLA_FLAGS``
overrides); importing the module changes no environment.
"""
import argparse
import json
import os
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import SHAPES, SHAPE_BY_NAME, cell_is_runnable
from repro.configs.registry import ARCHS, get_arch
from repro.launch.mesh import dp_axes, make_production_mesh
from repro.models import build_model
from repro.models.layers import build_param_specs
from repro.roofline import analysis
from repro.training.optimizer import AdamWConfig, init_opt_state, opt_state_specs
from repro.training.train_step import make_train_step

RESULTS_PATH = os.path.join(os.path.dirname(__file__), "../../../benchmarks/dryrun_results.json")
RESULTS_PATH = os.path.normpath(RESULTS_PATH)


# ---------------------------------------------------------------------------
# Sharding assignment
# ---------------------------------------------------------------------------

def _axis_size(mesh, axes):
    s = 1
    for a in axes if isinstance(axes, tuple) else (axes,):
        s *= mesh.shape[a]
    return s


def batch_specs(batch_struct, mesh):
    dp = dp_axes(mesh)
    dps = _axis_size(mesh, dp)

    def rule(leaf):
        spec = [None] * len(leaf.shape)
        if len(leaf.shape) >= 1 and leaf.shape[0] % dps == 0 and leaf.shape[0] > 1:
            spec[0] = dp
        return P(*spec)

    return jax.tree.map(rule, batch_struct)


def cache_specs_tree(cache_struct, mesh, batch: int, seq: int):
    """Cache sharding by size matching: batch dim -> dp axes; the cache
    sequence dim -> 'model' (flash-decoding style KV split); fall back to
    sharding the largest divisible trailing dim over 'model'."""
    dp = dp_axes(mesh)
    dps = _axis_size(mesh, dp)
    tps = mesh.shape["model"]

    def rule(leaf):
        shape = leaf.shape
        spec = [None] * len(shape)
        used_tp = False
        bi = next((i for i in range(1, len(shape)) if shape[i] == batch), None)
        if bi is not None and batch % dps == 0 and batch > 1:
            spec[bi] = dp
        si = next(
            (i for i in range(1, len(shape)) if shape[i] == seq and i != bi), None
        )
        if si is not None and seq % tps == 0:
            spec[si] = "model"
            used_tp = True
        if not used_tp:
            # largest trailing dim divisible by tp (e.g. SSM state heads)
            cands = [
                i
                for i in range(1, len(shape))
                if i != bi and spec[i] is None and shape[i] % tps == 0 and shape[i] >= tps
            ]
            if cands:
                best = max(cands, key=lambda i: shape[i])
                spec[best] = "model"
        return P(*spec)

    return jax.tree.map(rule, cache_struct)


def named(mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


# ---------------------------------------------------------------------------
# Cell lowering
# ---------------------------------------------------------------------------

def _depth_ladder(cfg):
    """(cfg_small1, cfg_small2, units1, units2, units_real) for the affine
    cost extrapolation: XLA cost_analysis counts a lax.scan body ONCE, so
    the full scanned program under-reports per-layer work. We compile two
    small *unrolled* depths and extrapolate cost(L) = a + b*L (verified in
    tests/test_roofline.py)."""
    import dataclasses as dc

    if cfg.family == "hybrid":
        g = cfg.attn_every
    elif cfg.family == "ssm":
        g = cfg.slstm_every
    else:
        g = 1
    base = cfg.first_dense_layers
    l1, l2 = base + g, base + 2 * g
    kw = dict(unroll=True)
    if cfg.family == "encdec":
        c1 = dc.replace(cfg, n_layers=1, encoder_layers=1, **kw)
        c2 = dc.replace(cfg, n_layers=2, encoder_layers=2, **kw)
        return c1, c2, 1, 2, cfg.n_layers
    c1 = dc.replace(cfg, n_layers=l1, **kw)
    c2 = dc.replace(cfg, n_layers=l2, **kw)
    return c1, c2, l1, l2, cfg.n_layers


def _lower_program(cfg, shape, mesh, model=None, act_constraints=True):
    """Build + lower the right step program for (cfg, shape) on mesh.

    ``act_constraints`` toggles the Megatron-style activation sharding
    layout (perf iteration 1); False reproduces the paper-faithful
    weights-only-sharded baseline recorded in dryrun_results_baseline.json.
    """
    import contextlib

    from repro.models.layers import LAYOUT, activation_sharding

    model = model or build_model(cfg)
    rng = jax.random.PRNGKey(0)
    params_struct = model.init_shapes(rng)
    fsdp = dp_axes(mesh)
    layout_token = LAYOUT.set("opt" if act_constraints else "baseline")
    param_specs = build_param_specs(params_struct, mesh, fsdp)
    act_ctx = (
        activation_sharding(fsdp, "model", mesh.shape["model"])
        if act_constraints
        else contextlib.nullcontext()
    )

    with mesh, act_ctx:
        if shape.kind == "train":
            opt_cfg = AdamWConfig()
            step_fn = make_train_step(model, opt_cfg)
            opt_struct = jax.eval_shape(init_opt_state, params_struct)
            opt_specs = opt_state_specs(param_specs, params_struct, mesh, fsdp)
            batch_struct = model.train_inputs(shape)
            b_specs = batch_specs(batch_struct, mesh)
            lowered = jax.jit(
                step_fn,
                in_shardings=(named(mesh, param_specs), named(mesh, opt_specs), named(mesh, b_specs)),
                out_shardings=(named(mesh, param_specs), named(mesh, opt_specs), None),
            ).lower(params_struct, opt_struct, batch_struct)
        elif shape.kind == "prefill":
            batch_struct = model.train_inputs(shape)
            b_specs = batch_specs(batch_struct, mesh)

            if cfg.family == "encdec":
                fwd = lambda p, b: model.impl.forward(p, b["tokens"], b["frames"])
            elif cfg.family == "vlm":
                fwd = lambda p, b: model.impl.forward(p, b["tokens"], b["patch_embeds"])
            else:
                fwd = lambda p, b: model.impl.forward(p, b["tokens"])
            lowered = jax.jit(
                fwd,
                in_shardings=(named(mesh, param_specs), named(mesh, b_specs)),
            ).lower(params_struct, batch_struct)
        else:  # decode / long-decode
            dec = model.decode_inputs(shape)
            c_specs = cache_specs_tree(dec["cache"], mesh, shape.global_batch, shape.seq_len)
            tok_spec = batch_specs({"t": dec["token"]}, mesh)["t"]
            lowered = jax.jit(
                model.decode_step,
                in_shardings=(
                    named(mesh, param_specs),
                    named(mesh, c_specs),
                    NamedSharding(mesh, tok_spec),
                    NamedSharding(mesh, P()),
                ),
                out_shardings=(None, named(mesh, c_specs)),
            ).lower(params_struct, dec["cache"], dec["token"], dec["pos"])

    LAYOUT.reset(layout_token)
    return lowered, params_struct


def _raw_costs(compiled):
    ca = compiled.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    coll = analysis.collective_bytes(compiled.as_text())
    return {
        "flops": float(ca.get("flops", 0.0)),
        "hbm_bytes": float(ca.get("bytes accessed", 0.0)),
        "coll_bytes": float(sum(coll.values())),
        "coll_by_kind": coll,
    }


def lower_cell(arch_name: str, shape_name: str, multi_pod: bool,
               extrapolate: bool = True, act_constraints: bool = True):
    cfg = get_arch(arch_name)
    shape = SHAPE_BY_NAME[shape_name]
    runnable, why = cell_is_runnable(cfg, shape)
    if not runnable:
        return {"status": "skipped", "reason": why}

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(np.prod(mesh.devices.shape))

    # 1) full-depth scanned program: THE compile proof + memory analysis
    t0 = time.time()
    lowered, params_struct = _lower_program(cfg, shape, mesh,
                                            act_constraints=act_constraints)
    compiled = lowered.compile()
    t_compile = time.time() - t0
    full_costs = _raw_costs(compiled)

    # 2) per-layer cost extrapolation from two small unrolled depths
    costs = dict(full_costs)
    extrap = None
    if extrapolate:
        try:
            c1, c2, l1, l2, lreal = _depth_ladder(cfg)
            k1 = _raw_costs(
                _lower_program(c1, shape, mesh, act_constraints=act_constraints)[0].compile()
            )
            k2 = _raw_costs(
                _lower_program(c2, shape, mesh, act_constraints=act_constraints)[0].compile()
            )
            costs = {}
            for key in ("flops", "hbm_bytes", "coll_bytes"):
                slope = (k2[key] - k1[key]) / (l2 - l1)
                costs[key] = k1[key] + slope * (lreal - l1)
            extrap = {"l1": l1, "l2": l2, "lreal": lreal,
                      "c1": {k: k1[k] for k in ("flops", "hbm_bytes", "coll_bytes")},
                      "c2": {k: k2[k] for k in ("flops", "hbm_bytes", "coll_bytes")}}
        except Exception as e:  # fall back to scanned-program numbers
            extrap = {"error": f"{type(e).__name__}: {e}"}
            costs = dict(full_costs)

    mf = analysis.model_flops_for(cfg, shape, params_struct)
    roof = analysis.Roofline(
        flops=costs["flops"],
        hbm_bytes=costs["hbm_bytes"],
        coll_bytes=costs["coll_bytes"],
        chips=chips,
        model_flops=mf,
    )
    counts = analysis.count_params(params_struct)

    mem = {}
    try:
        ma = compiled.memory_analysis()
        mem = {
            "argument_bytes": int(getattr(ma, "argument_size_in_bytes", 0)),
            "output_bytes": int(getattr(ma, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(ma, "temp_size_in_bytes", 0)),
            "generated_code_bytes": int(getattr(ma, "generated_code_size_in_bytes", 0)),
        }
    except Exception:
        pass

    return {
        "status": "ok",
        "arch": arch_name,
        "shape": shape_name,
        "mesh": list(mesh.devices.shape),
        "chips": chips,
        "kind": shape.kind,
        "compile_s": round(t_compile, 1),
        "params": counts,
        "memory_analysis": mem,
        "collectives": full_costs["coll_by_kind"],
        "scanned_program_costs": {k: full_costs[k] for k in ("flops", "hbm_bytes", "coll_bytes")},
        "extrapolation": extrap,
        "roofline": roof.to_dict(),
    }


# ---------------------------------------------------------------------------
# Mining-engine dry-run cell (the paper's own workload on the mesh)
# ---------------------------------------------------------------------------

def lower_mining(multi_pod: bool, n_vertices=65536, max_deg=64, frontier=1 << 20, k=5):
    from repro.core.distributed import mining_step_for_dryrun
    from repro.core.graph import DeviceGraph

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(np.prod(mesh.devices.shape))
    axes = dp_axes(mesh)
    n_shards = _axis_size(mesh, axes)
    sds = jax.ShapeDtypeStruct
    w = (n_vertices + 31) // 32
    g = DeviceGraph(
        labels=sds((n_vertices,), jnp.int32),
        nbr=sds((n_vertices, max_deg), jnp.int32),
        nbr_eid=sds((n_vertices, max_deg), jnp.int32),
        deg=sds((n_vertices,), jnp.int32),
        adj_bits=sds((n_vertices, w), jnp.uint32),
        edge_uv=sds((n_vertices * max_deg // 2, 2), jnp.int32),
        edge_labels=sds((n_vertices * max_deg // 2,), jnp.int32),
    )
    per = frontier // n_shards
    members = sds((n_shards, per, k), jnp.int32)
    n_valid = sds((n_shards, per), jnp.int32)
    quick_dict = sds((512, 3), jnp.int64)

    step = mining_step_for_dryrun(mesh, axes)
    gspec = DeviceGraph(
        labels=P(), nbr=P(), nbr_eid=P(), deg=P(),
        adj_bits=P("model"), edge_uv=P(), edge_labels=P(),
    )
    spec = P(axes)
    t0 = time.time()
    with mesh:
        lowered = jax.jit(
            step,
            in_shardings=(
                named(mesh, gspec),
                NamedSharding(mesh, spec),
                NamedSharding(mesh, spec),
                NamedSharding(mesh, P()),
            ),
        ).lower(g, members, n_valid, quick_dict)
        compiled = lowered.compile()
    roof = analysis.from_compiled(compiled, chips)
    return {
        "status": "ok",
        "arch": "arabesque-mining-step",
        "shape": f"frontier{frontier}_n{n_vertices}",
        "mesh": list(mesh.devices.shape),
        "chips": chips,
        "compile_s": round(time.time() - t0, 1),
        "collectives": analysis.collective_bytes(compiled.as_text()),
        "roofline": roof.to_dict(),
    }


# ---------------------------------------------------------------------------
# Driver with incremental cache
# ---------------------------------------------------------------------------

def load_results():
    if os.path.exists(RESULTS_PATH):
        with open(RESULTS_PATH) as f:
            return json.load(f)
    return {}


def save_results(res):
    os.makedirs(os.path.dirname(RESULTS_PATH), exist_ok=True)
    with open(RESULTS_PATH, "w") as f:
        json.dump(res, f, indent=1, default=float)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--mining", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--no-act-constraints", action="store_true",
                    help="paper-faithful weights-only-sharded baseline layout")
    ap.add_argument("--results", default=None,
                    help="alternate results JSON path")
    args = ap.parse_args()
    global RESULTS_PATH
    if args.results:
        RESULTS_PATH = args.results

    results = load_results()
    archs = [args.arch] if args.arch else sorted(ARCHS)
    shapes = [args.shape] if args.shape else [s.name for s in SHAPES]
    meshes = [False, True] if (args.both_meshes or args.all) else [args.multi_pod]

    if args.mining:
        for mp in meshes:
            key = f"mining|{'multi' if mp else 'single'}"
            if key in results and not args.force:
                continue
            print(f"[dryrun] {key} ...", flush=True)
            try:
                results[key] = lower_mining(mp)
            except Exception as e:
                results[key] = {"status": "error", "error": f"{type(e).__name__}: {e}"}
                traceback.print_exc()
            save_results(results)
        return

    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                key = f"{arch}|{shape}|{'multi' if mp else 'single'}"
                if key in results and results[key].get("status") in ("ok", "skipped") and not args.force:
                    continue
                print(f"[dryrun] {key} ...", flush=True)
                try:
                    results[key] = lower_cell(
                        arch, shape, mp,
                        act_constraints=not args.no_act_constraints,
                    )
                    st = results[key]["status"]
                    if st == "ok":
                        r = results[key]["roofline"]
                        print(
                            f"  ok compile={results[key]['compile_s']}s "
                            f"bottleneck={r['bottleneck']} "
                            f"frac={r['roofline_fraction']:.3f}",
                            flush=True,
                        )
                    else:
                        print(f"  {st}: {results[key].get('reason','')}", flush=True)
                except Exception as e:
                    results[key] = {
                        "status": "error",
                        "error": f"{type(e).__name__}: {e}",
                    }
                    print(f"  ERROR {type(e).__name__}: {e}", flush=True)
                save_results(results)

    n_ok = sum(1 for v in results.values() if v.get("status") == "ok")
    n_err = sum(1 for v in results.values() if v.get("status") == "error")
    print(f"[dryrun] done: {n_ok} ok, {n_err} errors, {len(results)} total cells")


if __name__ == "__main__":
    os.environ["XLA_FLAGS"] = os.environ.get(
        "REPRO_DRYRUN_XLA_FLAGS", "--xla_force_host_platform_device_count=512"
    )
    main()

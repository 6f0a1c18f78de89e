"""§Perf hillclimb measurement: for the three chosen (arch x shape) pairs,
lower the BASELINE layout (paper-faithful weights-only sharding, iteration
0) and the OPTIMIZED layout (activation constraints + ZeRO-1 + blocked
attention) and report both roofline term sets.

Run standalone (it forces 512 host devices):
    PYTHONPATH=src python -m benchmarks.bench_perf_ladder
Writes benchmarks/perf_ladder.json.
"""
import os
import dataclasses
import json

PAIRS = [
    # worst roofline fraction among big trains + collective-bound
    ("yi-34b", "train_4k"),
    # most collective-bound MoE (expert-parallel all-to-all path)
    ("deepseek-v2-236b", "train_4k"),
    # prefill: attention-quadratic dominant, memory/collective mix
    ("qwen2.5-14b", "prefill_32k"),
]

OUT = os.path.join(os.path.dirname(__file__), "perf_ladder.json")


def measure(arch, shape_name, act_constraints):
    from repro.configs.base import SHAPE_BY_NAME
    from repro.configs.registry import get_arch
    from repro.launch.dryrun import _depth_ladder, _lower_program, _raw_costs
    from repro.launch.mesh import make_production_mesh
    from repro.roofline import analysis

    cfg = get_arch(arch)
    shape = SHAPE_BY_NAME[shape_name]
    mesh = make_production_mesh()
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
    model = None
    from repro.models import build_model

    model = build_model(cfg)
    import jax

    ps = model.init_shapes(jax.random.PRNGKey(0))
    mf = analysis.model_flops_for(cfg, shape, ps)
    roof = analysis.Roofline(chips=256, model_flops=mf, **costs)
    return roof.to_dict()


def main():
    results = {}
    if os.path.exists(OUT):
        with open(OUT) as f:
            results = json.load(f)
    for arch, shape in PAIRS:
        for layout in ("baseline", "opt"):
            key = f"{arch}|{shape}|{layout}"
            if key in results:
                continue
            print(f"[perf] {key} ...", flush=True)
            try:
                results[key] = measure(arch, shape, act_constraints=(layout == "opt"))
                r = results[key]
                print(
                    f"  tc={r['t_compute_s']:.2f} tm={r['t_memory_s']:.2f} "
                    f"tx={r['t_collective_s']:.2f} frac={r['roofline_fraction']:.3f}",
                    flush=True,
                )
            except Exception as e:
                results[key] = {"error": f"{type(e).__name__}: {e}"}
                print("  ERROR", e, flush=True)
            with open(OUT, "w") as f:
                json.dump(results, f, indent=1, default=float)
    for k, v in results.items():
        if "error" not in v:
            print(f"{k}: frac={v['roofline_fraction']:.3f} "
                  f"bottleneck={v['bottleneck']}")


if __name__ == "__main__":
    os.environ["XLA_FLAGS"] = os.environ.get(
        "REPRO_DRYRUN_XLA_FLAGS", "--xla_force_host_platform_device_count=512"
    )
    main()

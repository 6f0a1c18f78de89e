"""Paper Table 3 / Fig. 8: scalability with worker count. Workers are
forced host devices in subprocesses (1, 2, 4, 8); speedup is relative to 1
worker, like the paper's Fig. 8 normalises to 5 servers.

A CPU-only bench: every child forces ``JAX_PLATFORMS=cpu`` host devices,
so its times are CPU times. It refuses to run where JAX's backend is a
TPU — the parent already holds the chip there, and a child could not
reach it. A child that fails fails the bench."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

from benchmarks.common import emit

SCRIPT = textwrap.dedent(
    """
    import json, time
    import jax
    from repro.core import graph as G
    from repro.core.apps import MotifsApp
    from repro.core.distributed import run_distributed, DistConfig

    n = len(jax.devices())
    mesh = jax.make_mesh((n,), ("data",))
    g = G.mico_like(scale=0.004, seed=11)
    app = MotifsApp(max_size=3)
    # warmup (compile)
    run_distributed(g, app, mesh, DistConfig(initial_capacity=1 << 15))
    t0 = time.perf_counter()
    res = run_distributed(g, app, mesh, DistConfig(initial_capacity=1 << 15))
    dt = time.perf_counter() - t0
    print("RESULT" + json.dumps({"n": n, "time_s": dt,
                                 "emb": res.stats.total_embeddings}))
    """
)


def main():
    import jax

    if jax.default_backend() == "tpu":
        raise RuntimeError(
            "bench_scalability forces host CPU devices in child processes; "
            "it is a CPU-only bench and does not run on a TPU backend"
        )
    times = {}
    for n in (1, 2, 4, 8):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
        env.setdefault("PYTHONPATH", "src")
        proc = subprocess.run(
            [sys.executable, "-W", "ignore", "-c", SCRIPT],
            env=env, capture_output=True, text=True, timeout=1200,
        )
        if proc.returncode != 0:
            raise RuntimeError(
                f"scalability child with {n} workers failed "
                f"(exit {proc.returncode}):\n{proc.stderr[-3000:]}"
            )
        line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT")][0]
        out = json.loads(line[len("RESULT"):])
        times[n] = out["time_s"]
        speedup = times[1] / out["time_s"] if 1 in times else 1.0
        emit(
            f"table3.motifs_{n}w",
            out["time_s"] * 1e6,
            f"speedup={speedup:.2f};emb={out['emb']}",
        )


if __name__ == "__main__":
    main()

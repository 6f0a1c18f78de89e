"""The control of the benchmark's check: the plain reference put in the
program's place with one guarantee of the configuration broken.

Every configuration states that each embedding is counted exactly once.
The control drops that guarantee the way a mining job without its
canonicality filter would: an embedding is reported once for every order
in which an expansion can reach its vertices. Each app's module
(``apps/<app>.py``) gives that answer as ``control(graph, params)``. A
check that passes the control cannot tell exactly-once counting from
duplicated work.

    python3 benchmarks/chip/control.py --workload citeseer.motifs3 \\
        --seed 7 8 9 --seconds 5

runs the cell with the control in the program's place once per seed, in
one process (on the chip, or anywhere with ``--no-tpu``), and prints each
run's result line, whose ``correct`` has to be false.
"""
from __future__ import annotations

import argparse
import json
import sys
from types import SimpleNamespace

import reference
import run


def mine(graph, job, config, traced):
    """One control job, shaped as the program's result. ``job`` is the
    cell's :class:`run.Job`."""
    g = reference.HostGraph(graph.n, graph.labels, graph.edges)
    patterns, embeddings = job.module.control(g, job.params)
    stats = SimpleNamespace(steps=[], cost_model={"source": "control"},
                            kernel_routes={}, total_embeddings=0)
    return SimpleNamespace(patterns=patterns, embeddings=embeddings,
                           stats=stats), None


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, nargs="+", required=True)
    p.add_argument("--seconds", type=float, default=5.0)
    p.add_argument("--no-tpu", action="store_true")
    args = p.parse_args(argv)
    cell = run.load_cell(args.workload)
    passed = 0
    for seed in args.seed:
        result = run.execute(cell, seed, args.seconds, False,
                             require_tpu=not args.no_tpu, mine=mine,
                             out=sys.stderr)
        print(json.dumps(dict(result, seed=seed)), flush=True)
        passed += bool(result["correct"])
    return 1 if passed else 0


if __name__ == "__main__":
    sys.exit(main())

"""Smoke run of the mining runtime on a TPU, through its normal entry points.

    python chip_smoke.py               # one chip: CiteSeer- and MiCo-shaped phases
    python chip_smoke.py --four-chips  # ShardMapBackend over four chips vs one

Every phase mines a graph generated from a seed by ``repro.core.graph``
(nothing is downloaded) through ``repro.core.run`` / ``SuperstepRuntime``
and compares the result with a host reference computed here in numpy,
independently of the code under test. Each mining job runs twice: with
the cost model's automatic choices, and with every Pallas kernel forced
on; the forced run must show each of its kernels traced as compiled in
``RunStats.kernel_routes``.

Phases (one chip):
  * ``citeseer``: ``citeseer_like(1.0)`` (3,312 vertices, 4,732 edges,
    6 labels — paper Table 1): motifs to size 3, cliques to size 4, FSM at
    support 300 with at most 3 edges (paper Table 2).
  * ``mico``: ``mico_like(MICO_SCALE)`` motifs to size 3; the total of
    connected 3-vertex subsets is checked against sum C(d, 2) -
    2 * triangles, and the forced-kernel patterns against the auto ones.

With ``--four-chips`` only the four-chip phase runs: ``ShardMapBackend``
with ``graph_partition=4`` on a mesh of four devices, motifs to size 3 and
FSM (support 100) on the CiteSeer-shaped graph, each compared with
``SerialBackend`` on one chip in the same process, both with every kernel
forced on.

Lines before the last are per-phase JSON records (wall and compile
seconds, the decision table, kernel routes, reference comparison). The
last line is ``{"ok": true, "device": {...}}`` when every phase passed.
Exits non-zero, printing no result, when JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
import traceback
from collections import defaultdict

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

#: the Pallas kernels a forced-kernel run must trace as compiled, per app
#: (FSM explores edges: Alg. 2's kernel exists for vertex mode only, and
#: its dispatch records ``jnp:edge-mode``).
FORCED = dict(use_pallas=True, compact_kernel=True, aggregate_kernel=True,
              canonical_placement="device", aggregate_bin="radix",
              device_aggregate=True, async_chunks=True)
#: frontier rows per chunk program. On TPU v5e a program's compile time
#: grows faster than linearly with its array lengths (a 1-D gather of
#: 131,072 indices compiles in 44 s, of 8,192 in 2.6 s), and every pow2
#: (chunk, capacity) bucket is its own program, so the smoke keeps chunks
#: small enough that compiling fits the run's time limit.
CHUNK = {"citeseer": 512, "mico": 256}
EXPECT_COMPILED = {
    "motifs": {"canonical_check", "prefix_sum", "radix_hist", "radix_rank",
               "canonical_refine"},
    "cliques": {"expand_canonical", "prefix_sum"},
    "fsm": {"prefix_sum", "radix_hist", "radix_rank", "canonical_refine"},
}


# ---------------------------------------------------------------------------
# host references (numpy; independent of the engine)
# ---------------------------------------------------------------------------

def _decode(code):
    """(nv, adjacency, labels) of a pattern code: w0 = nv | bits << 4 (pair
    a < b at bit b(b-1)/2 + a), w1/w2 = 8-bit labels."""
    w0, w1, w2 = (int(x) for x in code)
    nv, bits = w0 & 0xF, w0 >> 4
    adj = np.zeros((nv, nv), bool)
    for b in range(1, nv):
        for a in range(b):
            if (bits >> (b * (b - 1) // 2 + a)) & 1:
                adj[a, b] = adj[b, a] = True
    words = [(w1 >> (8 * i)) & 0xFF for i in range(4)]
    words += [(w2 >> (8 * i)) & 0xFF for i in range(4)]
    return nv, adj, [int(x) for x in words[:nv]]


def shape_key(code):
    """A label-aware key of a pattern with at most 4 vertices, named by its
    shape: vertex, edge, wedge (center first), triangle, 3-star (center
    first), 3-path (the smaller of its two readings)."""
    nv, adj, lab = _decode(code)
    deg = adj.sum(axis=1)
    n_edges = int(deg.sum()) // 2
    if nv == 1:
        return ("v", lab[0])
    if nv == 2:
        return ("e",) + tuple(sorted(lab))
    if nv == 3 and n_edges == 2:
        c = int(np.argmax(deg))
        return ("w", lab[c]) + tuple(sorted(lab[i] for i in range(3) if i != c))
    if nv == 3:
        return ("t",) + tuple(sorted(lab))
    if nv == 4 and n_edges == 3 and deg.max() == 3:
        c = int(np.argmax(deg))
        return ("s", lab[c]) + tuple(sorted(lab[i] for i in range(4) if i != c))
    if nv == 4 and n_edges == 3:
        end = int(np.flatnonzero(deg == 1)[0])
        order = [end]
        while len(order) < 4:
            nxt = [j for j in np.flatnonzero(adj[order[-1]]) if j not in order]
            order.append(int(nxt[0]))
        fwd = tuple(lab[i] for i in order)
        return ("p",) + min(fwd, fwd[::-1])
    raise ValueError(f"no reference shape for pattern {code}")


def _adjacency(g):
    indptr, indices, _ = g.csr()
    nbrs = [indices[indptr[v]:indptr[v + 1]] for v in range(g.n)]
    return indptr, indices, nbrs


def triangles(g):
    """(T, 3) sorted vertex triples of every triangle (u < v < w)."""
    _, _, nbrs = _adjacency(g)
    out = []
    for u, v in g.edges:
        u, v = int(min(u, v)), int(max(u, v))
        common = np.intersect1d(nbrs[u], nbrs[v], assume_unique=True)
        for w in common[common > v]:
            out.append((u, v, int(w)))
    return np.asarray(out, np.int64).reshape(-1, 3)


def reference_motifs(g):
    """Vertex-induced pattern counts to size 3 keyed by :func:`shape_key`."""
    lab = g.labels.astype(np.int64)
    n_lab = int(lab.max()) + 1
    out = defaultdict(int)
    for v in range(g.n):
        out[("v", int(lab[v]))] += 1
    for u, v in g.edges:
        out[("e",) + tuple(sorted((int(lab[u]), int(lab[v]))))] += 1
    # per-vertex neighbour label counts: all wedges, then remove the
    # closed ones (each triangle closes one wedge per center)
    nl = np.zeros((g.n, n_lab), np.int64)
    e = g.edges.astype(np.int64)
    np.add.at(nl, (e[:, 0], lab[e[:, 1]]), 1)
    np.add.at(nl, (e[:, 1], lab[e[:, 0]]), 1)
    for c in range(n_lab):
        rows = nl[lab == c]
        for a in range(n_lab):
            for b in range(a, n_lab):
                if a == b:
                    cnt = (rows[:, a] * (rows[:, a] - 1) // 2).sum()
                else:
                    cnt = (rows[:, a] * rows[:, b]).sum()
                if cnt:
                    out[("w", c, a, b)] += int(cnt)
    for tri in triangles(g):
        tl = [int(lab[x]) for x in tri]
        out[("t",) + tuple(sorted(tl))] += 1
        for i in range(3):
            rest = tuple(sorted(tl[j] for j in range(3) if j != i))
            out[("w", tl[i]) + rest] -= 1
    return {k: v for k, v in out.items() if v}


def reference_cliques(g):
    """size -> number of cliques, sizes 1..4 (sizes with none left out)."""
    _, _, nbrs = _adjacency(g)
    sets = [set(x.tolist()) for x in nbrs]
    tri = triangles(g)
    four = 0
    for u, v, w in tri:
        four += sum(1 for x in sets[u] & sets[v] & sets[w] if x > w)
    counts = {1: g.n, 2: g.m, 3: len(tri), 4: four}
    return {k: v for k, v in counts.items() if v}


def _domains_support(tuples, lab, aut):
    """Min-image supports of the labeled patterns embedded as ``tuples``.

    ``tuples`` (R, p) holds every injective map of one shape into the
    graph (one row per orientation); ``aut`` lists the shape's
    automorphisms as position permutations. A row is an isomorphism from
    the labeled pattern whose label tuple is the smallest over ``aut``
    exactly when its own label tuple is that smallest one; the domain of a
    position is the set of vertices such rows put there."""
    if len(tuples) == 0:
        return {}
    lt = lab[tuples]                                      # (R, p)
    cands = np.stack([lt[:, list(a)] for a in aut])       # (A, R, p)
    canon = cands[0]
    for c in cands[1:]:
        less = np.zeros(len(lt), bool)
        decided = np.zeros(len(lt), bool)
        for j in range(lt.shape[1]):
            lt_j = (c[:, j] < canon[:, j]) & ~decided
            gt_j = (c[:, j] > canon[:, j]) & ~decided
            less |= lt_j
            decided |= lt_j | gt_j
        canon = np.where(less[:, None], c, canon)
    keep = (lt == canon).all(axis=1)
    t, key = tuples[keep], lt[keep]
    uk, inv = np.unique(key, axis=0, return_inverse=True)
    inv = inv.reshape(-1)
    out = {}
    for q in range(len(uk)):
        rows = t[inv == q]
        out[tuple(int(x) for x in uk[q])] = min(
            len(np.unique(rows[:, p])) for p in range(rows.shape[1])
        )
    return out


def reference_fsm(g, support):
    """Frequent patterns with at most 3 edges and their min-image supports.

    Level by level: min-image support is anti-monotone, so every frequent
    pattern's sub-patterns are frequent and a level with no frequent
    pattern ends the search (the engine's level-wise pruning finds exactly
    these patterns)."""
    lab = g.labels.astype(np.int64)
    e = g.edges.astype(np.int64)
    indptr, indices, _ = _adjacency(g)
    deg = np.diff(indptr)

    def extend(paths, at):
        """Append every neighbour of column ``at`` to each row."""
        v = paths[:, at]
        rep = deg[v]
        rows = np.repeat(np.arange(len(paths)), rep)
        offs = np.arange(rep.sum()) - np.repeat(np.cumsum(rep) - rep, rep)
        nxt = indices[indptr[v][rows] + offs]
        return np.concatenate([paths[rows], nxt[:, None]], axis=1)

    def distinct(t):
        ok = np.ones(len(t), bool)
        for i in range(t.shape[1]):
            for j in range(i + 1, t.shape[1]):
                ok &= t[:, i] != t[:, j]
        return t[ok]

    def frequent(name, t, aut):
        return {
            (name,) + _shape_labels(name, labels): sup
            for labels, sup in _domains_support(t, lab, aut).items()
            if sup >= support
        }

    directed = np.concatenate([e, e[:, ::-1]])            # (2m, 2) u -> v
    found = frequent("e", directed, [(0, 1), (1, 0)])
    if not found:
        return found
    wedge = distinct(extend(directed, 1))                 # (u, c, w)
    level2 = frequent("w", wedge[:, [1, 0, 2]], [(0, 1, 2), (0, 2, 1)])
    found.update(level2)
    if not level2:
        return found
    path = distinct(extend(wedge, 2))                     # (a, b, c, d)
    star = distinct(extend(wedge[:, [1, 0, 2]], 0))       # center first
    # closed wedges are the triangles' orientations (edges are u < v)
    lo = np.minimum(wedge[:, 0], wedge[:, 2])
    hi = np.maximum(wedge[:, 0], wedge[:, 2])
    tri = wedge[np.isin(lo * g.n + hi, e[:, 0] * g.n + e[:, 1])]
    found.update(frequent("t", tri, [(0, 1, 2), (0, 2, 1), (1, 0, 2),
                                     (1, 2, 0), (2, 0, 1), (2, 1, 0)]))
    found.update(frequent("p", path, [(0, 1, 2, 3), (3, 2, 1, 0)]))
    found.update(frequent("s", star, [
        (0,) + p for p in ((1, 2, 3), (1, 3, 2), (2, 1, 3), (2, 3, 1),
                           (3, 1, 2), (3, 2, 1))
    ]))
    return found


def _shape_labels(name, labels):
    """The label part of :func:`shape_key` for a shape-ordered tuple."""
    if name in ("e", "t"):
        return tuple(sorted(labels))
    if name in ("w", "s"):
        return (labels[0],) + tuple(sorted(labels[1:]))
    return min(tuple(labels), tuple(labels)[::-1])


def keyed(patterns):
    out = {}
    for code, v in patterns.items():
        k = shape_key(code)
        if k in out:
            raise AssertionError(f"two patterns share key {k}")
        out[k] = int(v)
    return out


def compare(name, got, want):
    """Exact dict equality, with the first differences on failure."""
    if got == want:
        return {"reference": "match", "n_keys": len(want)}
    diff = sorted(
        (k, got.get(k), want.get(k))
        for k in set(got) | set(want) if got.get(k) != want.get(k)
    )
    raise AssertionError(f"{name}: {len(diff)} keys differ, e.g. {diff[:8]}")


# ---------------------------------------------------------------------------
# runs
# ---------------------------------------------------------------------------

class CompileClock:
    """Sums JAX's backend-compile durations while active."""

    def __init__(self, jax):
        self.total = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.total += duration
            self.count += 1


def mine(jax, label, graph, app, config, clock, backend=None):
    """One run through ``SuperstepRuntime``; its record is printed at once
    (so a later failed check still leaves the numbers behind)."""
    from repro.core import SuperstepRuntime

    t0, c0, n0 = time.perf_counter(), clock.total, clock.count
    rt = SuperstepRuntime(graph, app, config, backend)
    res = rt.run()
    jax.effects_barrier()
    rec = {
        "run": label,
        "wall_s": round(time.perf_counter() - t0, 3),
        "compile_s": round(clock.total - c0, 3),
        "n_compiles": clock.count - n0,
        "phase_walls_s": res.stats.phase_walls(),
        "n_steps": len(res.stats.steps),
        "decisions": res.stats.cost_model.get("source"),
        "table": {k: res.stats.cost_model.get(k) for k in (
            "async_chunks", "device_aggregate", "use_pallas",
            "compact_kernel", "aggregate_kernel", "aggregate_bin",
            "canonical_placement")},
        "kernel_routes": res.stats.kernel_routes,
        "embeddings": int(res.stats.total_embeddings),
    }
    print(json.dumps(rec, default=str), flush=True)
    return res, rt, rec


def check_routes(routes, expect):
    """Every expected kernel traced compiled; nothing interpreted."""
    interp = sorted(k for k, r in routes.items() if "interpret" in r)
    missing = sorted(k for k in expect
                     if "compiled" not in routes.get(k, ()))
    if interp or missing:
        raise AssertionError(
            f"kernels interpreted: {interp}; forced kernels not compiled: "
            f"{missing}; routes: {routes}"
        )


def run_pair(jax, name, graph, app, result_of, reference, clock,
             expect, chunk, forced_extra=None):
    """Auto run + forced-kernel run, both against ``reference``."""
    from repro.core import RunConfig

    auto, _, rec_auto = mine(jax, f"{name}/auto", graph, app,
                             RunConfig(chunk_size=chunk), clock)
    rec_auto.update(compare(f"{name}/auto", result_of(auto), reference))
    # fresh traces, so the forced run's route record is complete
    jax.clear_caches()
    forced_cfg = RunConfig(chunk_size=chunk, **FORCED, **(forced_extra or {}))
    forced, _, rec_forced = mine(jax, f"{name}/forced", graph, app,
                                 forced_cfg, clock)
    rec_forced.update(compare(f"{name}/forced", result_of(forced), reference))
    check_routes(rec_forced["kernel_routes"], expect)
    rec_forced["forced_compiled"] = sorted(expect)
    return {"auto": rec_auto, "forced": rec_forced}


def phase_citeseer(jax, clock):
    from repro.core import graph as G
    from repro.core.apps import CliquesApp, FSMApp, MotifsApp

    g = G.citeseer_like(1.0)
    out = {"graph": {"n": g.n, "m": g.m, "labels": int(g.labels.max()) + 1,
                     "max_degree": int(g.degrees().max())},
           "chunk_size": CHUNK["citeseer"]}
    t0 = time.perf_counter()
    ref_m = reference_motifs(g)
    ref_c = reference_cliques(g)
    ref_f = reference_fsm(g, 300)
    out["reference_s"] = round(time.perf_counter() - t0, 3)

    def motif_result(r):
        return keyed(r.patterns)

    def clique_result(r):
        return {k: len(v) for k, v in r.embeddings.items() if len(v)}

    out["motifs"] = run_pair(jax, "motifs", g, MotifsApp(max_size=3),
                             motif_result, ref_m, clock,
                             EXPECT_COMPILED["motifs"], CHUNK["citeseer"])
    out["cliques"] = run_pair(jax, "cliques", g, CliquesApp(max_size=4),
                              clique_result, ref_c, clock,
                              EXPECT_COMPILED["cliques"], CHUNK["citeseer"],
                              forced_extra={"fused_expand": True})
    out["fsm"] = run_pair(jax, "fsm", g, FSMApp(support=300, max_size=3),
                          motif_result, ref_f, clock,
                          EXPECT_COMPILED["fsm"], CHUNK["citeseer"])
    return out


def connected_triples(g):
    d = g.degrees().astype(np.int64)
    return int((d * (d - 1) // 2).sum() - 2 * len(triangles(g)))


def expansion_program_bytes(jax, g, chunk, out_cap):
    """Device bytes (arguments + outputs + temporaries) of the size-2
    expansion program on ``g`` — expand + canonicality + compaction of a
    ``chunk``-row chunk into ``out_cap`` children — from
    ``compiled.memory_analysis()``."""
    from repro.core import explore, to_device

    members = jax.ShapeDtypeStruct((chunk, 2), np.int32)
    n_valid = jax.ShapeDtypeStruct((chunk,), np.int32)
    compiled = explore.expand_and_compact.lower(
        to_device(g), members, n_valid, "vertex", out_cap,
    ).compile()
    ma = compiled.memory_analysis()
    return int(ma.temp_size_in_bytes + ma.argument_size_in_bytes
               + ma.output_size_in_bytes)


#: the MiCo-shaped scale. mico_like(0.1) with 4,096-row chunks needs 35 GB
#: in one expansion program (the padded (n, D) layout makes every (B, k)
#: int32 candidate table a 128-lane tile: 64x padding); at 0.02 with
#: 256-row chunks the auto run alone spends 469 s compiling. 0.01 keeps
#: the whole smoke inside its time limit.
MICO_SCALE = 0.01


def phase_mico(jax, clock):
    from repro.core import RunConfig
    from repro.core import graph as G
    from repro.core.apps import MotifsApp

    dev = jax.devices()[0]
    limit = dev.memory_stats()["bytes_limit"]
    chunk = CHUNK["mico"]
    g = G.mico_like(MICO_SCALE)
    out = {
        "scale": MICO_SCALE,
        "chunk_size": chunk,
        "graph": {"n": g.n, "m": g.m, "labels": int(g.labels.max()) + 1,
                  "max_degree": int(g.degrees().max())},
        "device_bytes_limit": int(limit),
        "cut": (f"mico_like({MICO_SCALE}) of the published 100,000 "
                "vertices: the padded (n, D) neighbour layout (ROADMAP "
                "A2/B1) and the compile time it brings set the cut, not "
                "the chip's memory"),
    }
    want = connected_triples(g)
    app = MotifsApp(max_size=3)

    def total3(r):
        return sum(c for code, c in r.patterns.items()
                   if (int(code[0]) & 0xF) == 3)

    auto, rt, rec_auto = mine(jax, "mico/auto", g, app,
                              RunConfig(chunk_size=chunk), clock)
    got = total3(auto)
    if got != want:
        raise AssertionError(f"mico auto: {got} connected triples, want {want}")
    rec_auto["connected_triples"] = got
    cap = int(rt.backend.capacity)
    out["expansion_program_bytes"] = {
        "out_cap": cap,
        "bytes": expansion_program_bytes(jax, g, chunk, cap),
    }
    jax.clear_caches()
    forced, _, rec_forced = mine(jax, "mico/forced", g, app,
                                 RunConfig(chunk_size=chunk, **FORCED), clock)
    if forced.patterns != auto.patterns:
        raise AssertionError("mico: forced-kernel patterns differ from auto")
    check_routes(rec_forced["kernel_routes"],
                 {"canonical_check", "prefix_sum", "radix_hist",
                  "radix_rank", "canonical_refine"})
    rec_forced["equals_auto"] = True
    out["peak_bytes_in_use"] = int(dev.memory_stats()["peak_bytes_in_use"])
    out["reference"] = {"connected_triples": want, "match": True}
    out["auto"], out["forced"] = rec_auto, rec_forced
    return out


def phase_four_chips(jax, clock):
    from jax.sharding import Mesh
    from repro.core import graph as G
    from repro.core.apps import FSMApp, MotifsApp
    from repro.core import RunConfig
    from repro.core.runtime import SerialBackend, ShardMapBackend

    devs = jax.devices()
    if len(devs) < 4:
        raise AssertionError(f"--four-chips needs 4 devices, found {len(devs)}")
    mesh = Mesh(np.array(devs[:4]), ("data",))
    g = G.citeseer_like(1.0)
    out = {}
    # FSM at support 100: 21 frequent edge patterns on this graph (at the
    # one-chip phase's 300 none is frequent, and the comparison is empty)
    for name, app in (("motifs", MotifsApp(max_size=3)),
                      ("fsm", FSMApp(support=100, max_size=3))):
        # every kernel forced on, as in the one-chip phase's forced runs:
        # the same programs on both sides, no calibration pilot to compile
        cfg = RunConfig(chunk_size=CHUNK["citeseer"], **FORCED)
        serial, _, rec_s = mine(jax, f"{name}/serial", g, app, cfg, clock,
                                SerialBackend())
        shard, rt, rec_w = mine(jax, f"{name}/shard_map", g, app,
                                dataclasses.replace(cfg, graph_partition=4),
                                clock, ShardMapBackend(mesh))
        placed = {str(d) for d in rt.backend.g.nbr_sh.sharding.device_set}
        if len(placed) != 4:
            raise AssertionError(f"{name}: shards on {sorted(placed)}")
        rec_w.update(compare(f"{name}/shard_map", keyed(shard.patterns),
                             keyed(serial.patterns)))
        rec_w["shard_devices"] = sorted(placed)
        out[name] = {"serial_1chip": rec_s, "shard_map_4chips": rec_w}
    return out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--four-chips", action="store_true",
                        help="run only the ShardMapBackend phase on 4 chips")
    args = parser.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{devices[0].platform!r}); nothing was run", file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(HERE, "src"))
    try:
        from repro.compile_cache import use_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not next to this script "
              f"({e})", file=sys.stderr)
        return 2
    cache = use_compile_cache(HERE)
    import repro.core  # noqa: F401  (enables x64 like every entry point)

    clock = CompileClock(jax)
    print(json.dumps({"devices": [str(d) for d in devices],
                      "compile_cache": cache}), flush=True)
    phases = ([("four_chips", phase_four_chips)] if args.four_chips
              else [("citeseer", phase_citeseer), ("mico", phase_mico)])
    failed = []
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            rec = {"phase": name, "ok": True, **fn(jax, clock)}
        except Exception as e:  # report the phase, keep going
            traceback.print_exc()
            rec = {"phase": name, "ok": False,
                   "error": f"{type(e).__name__}: {e}"[:2000]}
            failed.append(name)
        rec["wall_s"] = round(time.perf_counter() - t0, 3)
        print(json.dumps(rec, default=str), flush=True)
    if failed:
        print(f"chip_smoke: phases failed: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

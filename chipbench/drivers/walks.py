"""Closed-loop walk corpus: batches of ``walkers`` walks through
``DynamicWalkEngine.walk`` (the relay on a mesh), each batch's paths
fetched to the host before it counts.

Starts cycle through a seeded permutation of the vertices that have an
out-edge.  ``walk_steps_per_s`` is every hop taken in the returned
paths, over the whole window, the fetch of the last batch included.
"""

from __future__ import annotations

import time
from collections import deque

import numpy as np

from chipbench import gen, harness
from chipbench.reference import Reference, transition_z


def run(r: harness.Run) -> None:
    from repro.serve.dynwalk import DynamicWalkEngine

    cfg, mix = r.config, r.mix
    with r.span("cb.generate"):
        graph = gen.make_graph(cfg, r.seed)
    mesh = harness.make_mesh(r.cell["chips"])
    bcfg, state = harness.build_state(r, graph, mesh)
    params = harness.walk_params(mix)
    engine = DynamicWalkEngine(state, bcfg, params, seed=r.jax_seed,
                               mesh=mesh)
    del state
    W = mix["walkers"]
    deg = np.bincount(graph.src[graph.live], minlength=graph.num_vertices)
    perm = r.rng(2).permutation(np.flatnonzero(deg > 0)).astype(np.int32)

    def starts_of(b):
        return perm[(b * W + np.arange(W)) % len(perm)]

    # warm-up: the one program shape this cell runs
    for b in range(mix["warm_batches"]):
        np.asarray(engine.walk(starts_of(-1 - b)))

    # the batches the comparison reads: a seeded reservoir sample of
    # the window's batches, so that host memory does not grow with it
    rng = r.rng(3)
    k = mix["check_batches"]
    kept, hops, fetched, inflight = [], 0, 0, deque()

    def fetch():
        nonlocal hops, fetched
        b, paths = inflight.popleft()
        with r.span("cb.fetch"):
            host = np.asarray(paths)
        hops += int(np.count_nonzero(host[:, 1:] >= 0))
        if len(kept) < k:
            kept.append((b, host))
        else:
            j = rng.integers(fetched + 1)
            if j < k:
                kept[j] = (b, host)
        fetched += 1

    with r.window():
        t0 = time.perf_counter()
        b = 0
        while time.perf_counter() - t0 < r.seconds:
            with r.span("cb.dispatch"):
                inflight.append((b, engine.walk(starts_of(b))))
            b += 1
            if len(inflight) > mix["inflight"]:
                fetch()
        while inflight:
            fetch()
    r.e2e["walk_steps_per_s"] = hops / r.window_s
    r.attempted = fetched * W
    r.failed = 0
    r.counters.update(batches=fetched, hops=hops,
                      alias_entries=bcfg.num_inter)
    r.read_memory_peak()
    del engine

    # the comparison
    ref = Reference(graph)
    bad, steps, pits = 0, 0, []
    for b, paths in kept:
        x, n, pit = ref.check_walks(graph.live, paths, starts_of(b),
                                    params.stop_prob, rng)
        bad, steps = bad + x, steps + n
        pits.append(pit)
    r.counters["checked_steps"] = steps
    r.check("bad_hops", bad, mix["limits"]["bad_hops"])
    r.check("transition_z", transition_z(np.concatenate(pits)),
            mix["limits"]["transition_z"])

"""Closed-loop batched ingest: stationary mixed rounds through
``DynamicWalkEngine.ingest`` with the guard on and its accounting
deferred, settled by ``drain_guard`` every ``drain_every`` rounds.

Each round is made on the host while the device applies the one
before; at most ``inflight`` rounds are queued on the device.
``updates_per_s`` is the valid lanes offered in the window over the
window, which ends when the state is ready.
"""

from __future__ import annotations

import time
from collections import deque

import numpy as np

from chipbench import gen, harness
from chipbench.reference import Reference


def _fmix32(h):
    import jax.numpy as jnp
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    return h ^ (h >> 16)


def row_digest(nbr, bias, deg):
    """The reference's order-free row digest, on the device: per vertex
    the wrapping sum of ``edge_hash`` over its live slots."""
    import jax.numpy as jnp
    live = jnp.arange(nbr.shape[1])[None, :] < deg[:, None]
    h = _fmix32(nbr.astype(jnp.uint32) * jnp.uint32(0x9E3779B1)
                + _fmix32(bias.astype(jnp.uint32) ^ jnp.uint32(0x5BD1E995)))
    return jnp.sum(jnp.where(live, h, jnp.uint32(0)), axis=1,
                   dtype=jnp.uint32)


def fetch_rows(state, verts):
    """Host copies of the sampled vertices' tables."""
    import jax
    import jax.numpy as jnp
    idx = jnp.asarray(verts)
    return jax.device_get({
        "nbr": state.nbr[idx], "bias": state.bias[idx],
        "deg": state.deg[idx], "gsize": state.gsize[idx],
        "digitsum": state.digitsum[idx], "gtype": state.gtype[idx],
        "gmem": state.gmem[idx], "prob": state.itable.prob[idx],
        "alias": state.itable.alias[idx]})


def run(r: harness.Run) -> None:
    import jax
    import jax.numpy as jnp
    from repro.serve.dynwalk import DynamicWalkEngine

    cfg, mix = r.config, r.mix
    with r.span("cb.generate"):
        graph = gen.make_graph(cfg, r.seed, holdout=mix["holdout"])
    bcfg, state = harness.build_state(r, graph)
    engine = DynamicWalkEngine(state, bcfg, guard=True, defer_guard=True,
                               seed=r.jax_seed)
    del state
    stream = gen.StationaryStream(graph, cfg["capacity"], r.rng(2))
    live = graph.live.copy()            # the reference's model
    n_ins = int(round(mix["lanes"] * mix["insert_share"]))
    n_del = mix["lanes"] - n_ins
    touched = np.zeros(graph.num_vertices, bool)
    ldeg = np.bincount(graph.src[live], minlength=graph.num_vertices)
    sums = []                            # per round: applied + rejected
    least_bytes = [0]
    fixed = 8 + 2 * 2 * bcfg.num_radix * 4 + 2 * bcfg.num_inter * 8

    def ingest(batch):
        """Apply one round to the model and the program; adds the
        round's least HBM bytes: each affected vertex's live row read
        and written once (neighbour id and bias per edge), its degree,
        its group counters (size and digit sum) and its alias row
        (probability and redirect), each read and written once."""
        ins, u, v, w, ids = batch
        gen.apply_lanes(live, ins, ids)
        touched[u] = True
        aff = np.unique(u)
        before = ldeg[aff].sum()
        np.add.at(ldeg, u, np.where(ins, 1, -1))
        least_bytes[0] += int(8 * (before + ldeg[aff].sum())
                              + fixed * len(aff))
        st = engine.ingest(jnp.asarray(ins), jnp.asarray(u),
                           jnp.asarray(v), jnp.asarray(w))
        sums.append(jnp.concatenate([st.ins_applied[None],
                                     st.del_applied[None], st.rejected]))

    # warm-up: the update program, the classifier and the drain
    for _ in range(mix["warm_rounds"]):
        ingest(stream.batch(n_ins, n_del))
    engine.drain_guard()
    jax.block_until_ready(engine.state)
    warm = len(sums)
    least_bytes[0] = 0

    nxt = stream.batch(n_ins, n_del)
    queued = deque()
    with r.window():
        t0 = time.perf_counter()
        rounds = 0
        while time.perf_counter() - t0 < r.seconds:
            with r.span("cb.dispatch"):
                ingest(nxt)
            rounds += 1
            queued.append(sums[-1])
            if rounds % mix["drain_every"] == 0:
                with r.span("cb.drain"):
                    engine.drain_guard()
            with r.span("cb.generate"):
                nxt = stream.batch(n_ins, n_del)
            if len(queued) > mix["inflight"]:
                with r.span("cb.wait"):
                    queued.popleft().block_until_ready()
        with r.span("cb.wait"):
            jax.block_until_ready(engine.state)
    lanes = rounds * mix["lanes"]
    r.e2e["updates_per_s"] = lanes / r.window_s
    totals = np.asarray(jnp.stack(sums)).astype(np.int64)
    r.attempted = lanes
    r.failed = int(totals[warm:, 2:].sum())
    r.read_memory_peak()
    r.counters.update(rounds=rounds, lanes=lanes,
                      update_least_bytes=least_bytes[0])

    # the comparison
    engine.drain_guard()
    g = engine.guard
    verts = np.sort(r.rng(3).choice(np.flatnonzero(touched),
                                    size=min(mix["check_vertices"],
                                             int(touched.sum())),
                                    replace=False))
    dig = np.asarray(jax.jit(row_digest)(engine.state.nbr, engine.state.bias,
                                         engine.state.deg))
    deg = np.asarray(engine.state.deg).astype(np.int64)
    rows = fetch_rows(engine.state, verts)
    guard = (g.ingested, g.accepted, g.quarantined, len(g.pending))
    del engine
    ref = Reference(graph)
    want_dig, want_deg = ref.row_digest(live)
    rows_bad = int(np.sum((dig != want_dig) | (deg != want_deg)))
    offered = len(sums) * mix["lanes"]
    want = np.array([len(sums) * n_ins, len(sums) * n_del])
    stats_gap = int(np.abs(totals[:, :2].sum(0) - want).sum()
                    + totals[:, 2:].sum())
    guard_gap = abs(guard[0] - offered) + abs(guard[1] - offered) \
        + guard[2] + guard[3]
    space_bad, alias_gap = ref.check_space(live, verts, rows,
                                           bcfg.num_radix)
    lim = mix["limits"]
    r.check("rows_bad", rows_bad, lim["rows_bad"])
    r.check("stats_gap", stats_gap, lim["stats_gap"])
    r.check("guard_gap", guard_gap, lim["guard_gap"])
    r.check("space_bad", space_bad, lim["space_bad"])
    r.check("alias_gap", alias_gap, lim["alias_gap"])


"""Open-loop serving through ``ServingScheduler``: PPR queries with
Poisson arrivals from Zipf-ranked start vertices, beside a stream of
small mixed update batches, over a guarded engine.

The whole schedule (arrival times, starts, update batches) is made from
the seed before the window.  In the window the loop submits each
request when it is due, ticks the scheduler every ``tick_ms`` and polls
it every millisecond.  ``walk_p95_ms`` is the 95th percentile, over every
query due in the window, of due time to harvest; a refused query counts
as having waited the whole window.  ``update_visible_p95_ms`` is, over
every update batch admitted in the window, the due time to the harvest
of the first query stamped with a generation that holds the batch.  A
batch that no query of the window saw is read by a probe query sent
once the scheduler has drained, so a stalled update path shows in the
tail rather than falling out of it.
"""

from __future__ import annotations

import time

import numpy as np

from chipbench import gen, harness
from chipbench.reference import Reference, transition_z


def schedule(r: harness.Run, graph: gen.Graph, seconds: float):
    """Queries ``(times, starts)`` and update batches ``(times, batches)``
    over ``[0, seconds)``, from the seed."""
    mix = r.mix
    rng = r.rng(2)
    deg = np.bincount(graph.src[graph.live], minlength=graph.num_vertices)
    q_t = gen.poisson_times(rng, mix["rate_qps"], seconds)
    q_s = gen.zipf_starts(rng, np.flatnonzero(deg > 0), len(q_t),
                          mix["zipf_s"])
    every = mix["update_batch"] / mix["update_edges_per_s"]
    u_t = np.arange(0.0, seconds, every)
    stream = gen.StationaryStream(graph, r.config["capacity"], r.rng(3),
                                  gap=mix["update_lanes"])
    n_ins = mix["update_batch"] // 2
    batches = [stream.batch(n_ins, mix["update_batch"] - n_ins)
               for _ in u_t]
    return q_t, q_s, u_t, batches


def due_latency_ms(due, rid_of, harvest, seconds):
    """Due time to harvest of each query, in ms; a refused query
    (``rid_of < 0``) or one never harvested waited the whole window."""
    lat = np.full(len(due), seconds * 1e3)
    for i in np.flatnonzero(rid_of >= 0):
        if rid_of[i] in harvest:
            lat[i] = (harvest[rid_of[i]][0] - due[i]) * 1e3
    return lat


def visible_ms(due, lanes, cum, results, gave_up=np.inf):
    """Staleness of each admitted update batch, in ms: from its due time
    to the first harvest of a result whose generation holds it.

    ``lanes`` are the batches' lane counts in admission order, ``cum[g]``
    the admitted lanes that generation ``g`` holds, ``results`` the
    ``(harvest time, generation)`` of every served query, the probe
    included.  A batch that no result holds reads as visible at
    ``gave_up``, the time the run stopped waiting."""
    gen_of = np.searchsorted(cum, np.cumsum(lanes), side="left")
    first_at = np.full(len(cum) + 1, np.inf)
    for t, g in results:
        first_at[g] = min(first_at[g], t)
    first_at = np.minimum.accumulate(first_at[::-1])[::-1]  # at gen >= g
    return (np.minimum(first_at[gen_of], gave_up) - np.asarray(due)) * 1e3


def run(r: harness.Run) -> None:
    import jax
    import jax.numpy as jnp
    from repro.serve.dynwalk import DynamicWalkEngine
    from repro.serve.scheduler import (SchedulerConfig, ServingScheduler,
                                       UpdateOp)

    cfg, mix = r.config, r.mix
    with r.span("cb.generate"):
        graph = gen.make_graph(cfg, r.seed, holdout=mix["holdout"])
        q_t, q_s, u_t, batches = schedule(r, graph, r.seconds)
    bcfg, state = harness.build_state(r, graph)
    params = harness.walk_params(mix)
    buckets = tuple(mix["walk_buckets"])
    engine = DynamicWalkEngine(state, bcfg, params, guard=True,
                               defer_guard=True, walk_buckets=buckets,
                               seed=r.jax_seed)
    del state
    # warm-up: one empty update window (update program, classifier,
    # tally) and one walk per bucket; neither changes the graph
    L = mix["update_lanes"]
    engine.ingest(jnp.ones(L, bool), jnp.zeros(L, jnp.int32),
                  jnp.zeros(L, jnp.int32), jnp.ones(L, jnp.int32), n_valid=0)
    engine.drain_guard()
    for b in buckets:
        np.asarray(engine.walk(np.full(b, q_s[0] if len(q_s) else 0,
                                       np.int32)))
    # the engine pads a cohort of n starts to its bucket and slices the
    # paths back: warm those small programs for every cohort size
    per = mix["walks_per_query"]
    for n in range(per, buckets[-1] + 1, per):
        b = min(x for x in buckets if x >= n)
        if b != n:
            jnp.concatenate([jnp.zeros((n,), jnp.int32),
                             jnp.full((b - n,), 0, jnp.int32)])
            jnp.zeros((b, mix["length"] + 1), jnp.int32)[:n] \
                .block_until_ready()
    sched = ServingScheduler(engine, SchedulerConfig(
        update_lanes=L, max_update_delay=mix["max_update_delay"],
        max_walk_queue=mix["max_walk_queue"],
        max_update_queue=mix["max_update_queue"],
        max_inflight=mix["max_inflight"]))

    nq, nu = len(q_t), len(u_t)
    rid_of = np.full(nq, -1, np.int64)
    q_lag = np.zeros(nq)
    u_ok = np.zeros(nu, bool)
    harvest = {}                  # rid -> (time, generation, paths)
    tick_s = mix["tick_ms"] / 1e3
    clock = time.perf_counter

    def collect(res, t):
        for x in res:
            harvest[x.rid] = (t, x.generation, x.paths)

    with r.window():
        t0 = clock()
        iq = iu = 0
        next_tick = 0.0
        while True:
            now = clock() - t0
            while iq < nq and q_t[iq] <= now:
                with r.span("cb.submit"):
                    rid = sched.submit_walk(np.full(mix["walks_per_query"],
                                                    q_s[iq], np.int32))
                q_lag[iq] = clock() - t0 - q_t[iq]
                rid_of[iq] = -1 if rid is None else rid
                iq += 1
            while iu < nu and u_t[iu] <= now:
                ins, u, v, w, _ = batches[iu]
                with r.span("cb.submit"):
                    u_ok[iu] = sched.submit_update(ins, u, v, w)
                iu += 1
            if now >= next_tick:
                with r.span("cb.tick"):
                    sched.tick()
                next_tick = now + tick_s
            with r.span("cb.poll"):
                res = sched.poll()
            collect(res, clock() - t0)
            if iq == nq and iu == nu and now >= r.seconds:
                admitted = int(np.sum(rid_of >= 0))
                if len(harvest) >= admitted or now > r.seconds + 60:
                    break
            nxt = min(next_tick, q_t[iq] if iq < nq else np.inf,
                      u_t[iu] if iu < nu else np.inf, now + 1e-3)
            pause = nxt - (clock() - t0)
            if pause > 0:
                time.sleep(pause)
        t_served = clock() - t0
        with r.span("cb.drain"):    # flush the last update windows
            collect(sched.drain(), clock() - t0)
        # a probe on the drained generation reads the batches that no
        # query of the window saw
        probe = sched.submit_walk(np.full(mix["walks_per_query"], q_s[-1],
                                          np.int32))
        with r.span("cb.drain"):
            collect(sched.drain(), clock() - t0)
        t_end = clock() - t0
    r.read_memory_peak()

    lat = due_latency_ms(q_t, rid_of, harvest, r.seconds)
    r.e2e["walk_p95_ms"] = harness.percentile(lat, 95)
    # generation g holds the first cum[g] admitted update lanes
    cum = np.concatenate([[0], np.cumsum(
        [op.n_valid for op in sched.trace if isinstance(op, UpdateOp)])])
    ok_idx = np.flatnonzero(u_ok)
    window_results = [(t, g) for rid, (t, g, _) in harvest.items()
                      if rid != probe]
    lanes = [len(batches[i][0]) for i in ok_idx]
    probed = [harvest[probe][:2]] if probe in harvest else []
    vis = visible_ms(u_t[ok_idx], lanes, cum, window_results + probed,
                     gave_up=t_end)
    unseen = int(np.sum(~np.isfinite(visible_ms(u_t[ok_idx], lanes, cum,
                                                window_results))))
    r.e2e["update_visible_p95_ms"] = harness.percentile(vis, 95)
    g = engine.guard
    r.attempted = nq + nu
    r.failed = int(np.sum(rid_of < 0) + np.sum(~u_ok) + g.quarantined
                   + len(g.pending))
    r.counters.update(
        queries=nq, updates=nu, generations=len(cum) - 1,
        served_after_s=t_served - r.seconds,
        gen_lag_p95_ms=harness.percentile(q_lag, 95) * 1e3)
    r.log(f"serve: {nq} queries, {nu} update batches, {len(cum) - 1} "
          f"update windows, {int(np.sum(rid_of < 0))} queries refused, "
          f"{unseen} batches seen only by the probe")

    # the comparison
    from chipbench.drivers.ingest import row_digest
    engine.drain_guard()
    dig = np.asarray(jax.jit(row_digest)(engine.state.nbr, engine.state.bias,
                                         engine.state.deg))
    deg = np.asarray(engine.state.deg).astype(np.int64)
    guard = (g.ingested, g.accepted, g.quarantined, len(g.pending))
    sched.close()
    del engine, sched
    lane_ins = np.concatenate([batches[i][0] for i in ok_idx])
    lane_ids = np.concatenate([batches[i][4] for i in ok_idx])
    ref = Reference(graph)
    live = graph.live.copy()
    rng = r.rng(4)
    bad, pits, applied = 0, [], 0
    lost = int(np.sum(rid_of >= 0)) + (probe is not None) - len(harvest)
    start_of = {int(rid_of[i]): int(q_s[i]) for i in range(nq)}
    start_of[probe] = int(q_s[-1])
    # a seeded sample of the answered queries, the probe always in it
    answered = sorted(harvest)
    pick = set(rng.choice(answered, size=min(mix["check_queries"],
                                             len(answered)),
                          replace=False).tolist()) | ({probe} & set(harvest))
    by_gen = sorted(((k, v) for k, v in harvest.items() if k in pick),
                    key=lambda kv: kv[1][1])
    i = 0
    while i < len(by_gen):
        gnum = by_gen[i][1][1]
        sl = slice(applied, cum[gnum])
        gen.apply_lanes(live, lane_ins[sl], lane_ids[sl])
        applied = cum[gnum]
        rids, paths = [], []
        while i < len(by_gen) and by_gen[i][1][1] == gnum:
            rids.append(by_gen[i][0])
            paths.append(by_gen[i][1][2])
            i += 1
        starts = np.repeat([start_of[x] for x in rids],
                           [len(p) for p in paths])
        x, _, pit = ref.check_walks(live, np.concatenate(paths), starts,
                                    params.stop_prob, rng)
        bad += x
        pits.append(pit)
    gen.apply_lanes(live, lane_ins[applied:], lane_ids[applied:])
    want_dig, want_deg = ref.row_digest(live)
    rows_bad = int(np.sum((dig != want_dig) | (deg != want_deg)))
    offered = len(lane_ids)
    guard_gap = abs(guard[0] - offered) + abs(guard[1] - offered) \
        + guard[2] + guard[3]
    lim = mix["limits"]
    r.check("lost", lost, lim["lost"])
    r.check("bad_hops", bad, lim["bad_hops"])
    r.check("transition_z", transition_z(np.concatenate(pits)),
            lim["transition_z"])
    r.check("rows_bad", rows_bad, lim["rows_bad"])
    r.check("guard_gap", guard_gap, lim["guard_gap"])

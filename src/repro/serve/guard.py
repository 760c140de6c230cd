"""Validated ingestion: device-side update classification + quarantine.

The §5.2 update pipelines (``core/updates.py:batched_update`` and the
update megakernel) are *internally* safe — no lane can corrupt the row
tables, and rejects are counted per reason in ``UpdateStats.rejected``
— but at serving time "dropped and counted" is not enough: operators
need to know *which* updates died and why, capacity overflow should
degrade gracefully instead of losing edges, and policy decisions
(duplicate-edge handling, weight hygiene) do not belong inside the
bit-exact-pinned kernels.  This module is that layer (DESIGN.md §11):

* ``make_classifier`` — a jit-able device-side pre-pass that assigns
  every lane of an update round a reason code from the shared taxonomy
  (``core/updates``): ``R_OK`` / ``R_VERTEX`` / ``R_WEIGHT`` /
  ``R_DUP`` / ``R_ABSENT`` / ``R_CAPACITY``.  It replicates the batched
  oracle's stage-1/2 ordering (segmented insert ranks against current
  degrees, post-insert delete locate), so a lane it marks OK is
  *guaranteed* to apply — after the guard, the engine-level
  ``rejected`` counters stay zero.
* ``IngestGuard`` — the host-side bookkeeper: rejects go to a
  quarantine buffer as structured ``QuarantineRecord``s; capacity
  overflows spill to a bounded-retry pending queue that is re-attempted
  after rounds that applied deletes (the only event that can free a
  slot).  Conservation invariant, checked by tests every round:
  ``accepted + quarantined + len(pending) == ingested``.

``DynamicWalkEngine(guard=...)`` wires both into the serving loop; the
classifier is pure jnp, so in sharded mode it runs over the vertex-
partitioned state unchanged (GSPMD partitions the row gathers) while
the guard keeps checking v against the *global* vertex count — the one
check the shard-local engine pipelines cannot do (DESIGN.md §10).
"""

from __future__ import annotations

import functools
from collections import deque
from typing import Deque, List, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import radix
from repro.core.dyngraph import BingoConfig
from repro.core.updates import (NUM_REASONS, R_ABSENT, R_CAPACITY, R_DUP,
                                R_OK, R_VERTEX, R_WEIGHT, REASON_NAMES)

__all__ = ["GuardPolicy", "QuarantineRecord", "PendingInsert",
           "IngestGuard", "make_classifier", "valid_lanes"]


class GuardPolicy(NamedTuple):
    """Serving-side ingestion policy (DESIGN.md §11).

    ``reject_duplicates=False`` by default: BINGO is a multigraph engine
    (duplicate deletes resolve earliest-version-first), so duplicate
    inserts are legal — flip it to enforce simple-graph semantics.
    ``max_retries=0`` sends capacity overflows straight to quarantine
    instead of the pending queue.
    """
    reject_duplicates: bool = False
    max_retries: int = 4          # per-edge retry budget after overflow
    retry_batch: int = 64         # fixed lane count of a retry round


class QuarantineRecord(NamedTuple):
    round: int       # rounds_ingested at classification time
    is_insert: bool
    u: int
    v: int
    w: float
    reason: int      # R_* code (``REASON_NAMES[reason]`` for the label)


class PendingInsert(NamedTuple):
    round: int       # round that first saw the edge
    u: int
    v: int
    w: float
    retries_left: int


def valid_lanes(cfg: BingoConfig, u, v):
    """Endpoint-range mask against the GLOBAL vertex count.

    The one guard check shard-local pipelines cannot perform: their
    ``cfg.num_vertices`` is the shard size while neighbor ids stay
    global.  Used by the classifier below, which checks a sharded
    engine's lanes against the global ``cfg``.
    """
    V = cfg.num_vertices
    return (u >= 0) & (u < V) & (v >= 0) & (v < V)


def make_classifier(cfg: BingoConfig, policy: GuardPolicy = GuardPolicy()):
    """Build the jitted device-side pre-pass.

    Returns ``classify(state, is_insert, u, v, w) -> (B,) int32`` reason
    codes.  Mirrors ``batched_update``'s ordering exactly — segmented
    insert ranks against current degrees decide ``R_CAPACITY``; deletes
    are located against the row table *after* this round's accepted
    inserts (so deleting an edge inserted earlier in the same round is
    OK, matching §5.2 insert-before-delete staging).
    """
    V, C = cfg.num_vertices, cfg.capacity

    def reason_codes(state, is_insert, u, v, w):
        B = u.shape[0]
        u = jnp.asarray(u, jnp.int32)
        v = jnp.asarray(v, jnp.int32)
        idx = jnp.arange(B, dtype=jnp.int32)

        valid = valid_lanes(cfg, u, v)
        if cfg.fp_bias:
            bad_w = ~jnp.isfinite(w) | (w <= 0)
        else:
            bad_w = jnp.asarray(w, jnp.int32) < 1
        bad_w = bad_w & is_insert & valid       # delete lanes ignore w
        uc = jnp.where(valid, u, 0)             # wrap-safe gathers

        ins0 = is_insert & valid & ~bad_w
        if policy.reject_duplicates:
            live = (jnp.arange(C, dtype=jnp.int32)[None, :]
                    < state.deg[uc][:, None])
            in_state = jnp.any(
                (state.nbr[uc] == v[:, None]) & live, axis=-1) & ins0
            ku = jnp.where(ins0, u, V)
            kv = jnp.where(ins0, v, -1)
            ordP = jnp.lexsort((kv, ku))
            ku_s, kv_s = ku[ordP], kv[ordP]
            firstP = jnp.concatenate(
                [jnp.ones((1,), bool),
                 (ku_s[1:] != ku_s[:-1]) | (kv_s[1:] != kv_s[:-1])])
            repeat = jnp.zeros((B,), bool).at[ordP].set(
                ~firstP & (ku_s < V))
            dup = ins0 & (in_state | repeat)
        else:
            dup = jnp.zeros((B,), bool)
        ins1 = ins0 & ~dup

        # -- capacity: the oracle's stage-1 segmented ranks --
        su = jnp.where(ins1, u, V)
        order = jnp.argsort(su)
        su_s, v_s = su[order], v[order]
        first = jnp.concatenate(
            [jnp.ones((1,), bool), su_s[1:] != su_s[:-1]])
        rank = idx - jax.lax.cummax(jnp.where(first, idx, -1), axis=0)
        off = state.deg[jnp.minimum(su_s, V - 1)] + rank
        okA = (su_s < V) & (off < C)
        overflow = jnp.zeros((B,), bool).at[order].set((su_s < V) & ~okA)

        # -- absent deletes: locate against the post-insert rows --
        tgt = jnp.where(okA, off, C)
        nbr2 = state.nbr.at[su_s, tgt].set(v_s, mode="drop")
        deg2 = state.deg.at[jnp.where(okA, su_s, V)].add(1, mode="drop")
        del0 = (~is_insert) & valid
        du = jnp.where(del0, u, V)
        dv = jnp.where(del0, v, -1)
        ordD = jnp.lexsort((dv, du))
        du_s, dv_s = du[ordD], dv[ordD]
        firstD = jnp.concatenate(
            [jnp.ones((1,), bool),
             (du_s[1:] != du_s[:-1]) | (dv_s[1:] != dv_s[:-1])])
        rankD = idx - jax.lax.cummax(jnp.where(firstD, idx, -1), axis=0)
        rows = nbr2[jnp.minimum(du_s, V - 1)]
        validD = (jnp.arange(C, dtype=jnp.int32)[None, :]
                  < deg2[jnp.minimum(du_s, V - 1)][:, None])
        m = (rows == dv_s[:, None]) & validD & (du_s < V)[:, None]
        cnt = jnp.cumsum(m, axis=-1)
        hit = jnp.any(m & (cnt == (rankD + 1)[:, None]), axis=-1)
        found = jnp.zeros((B,), bool).at[ordD].set(hit & (du_s < V))
        absent = del0 & ~found

        reasons = jnp.full((B,), R_OK, jnp.int32)
        reasons = jnp.where(~valid, R_VERTEX, reasons)
        reasons = jnp.where(bad_w, R_WEIGHT, reasons)
        reasons = jnp.where(dup, R_DUP, reasons)
        reasons = jnp.where(ins1 & overflow, R_CAPACITY, reasons)
        reasons = jnp.where(absent, R_ABSENT, reasons)
        return reasons

    @jax.jit
    def classify(state, is_insert, u, v, w):
        with jax.named_scope("guard.classify"):
            return reason_codes(state, is_insert, u, v, w)

    return classify


class IngestGuard:
    """Host-side quarantine buffer + pending-overflow queue.

    One per guarded engine.  ``account`` ingests a classified round's
    reason codes; ``take_retry`` hands back a fixed-shape retry batch of
    pending inserts once deletes have freed capacity; ``settle_retry``
    routes each retried lane to accepted / back-to-pending / quarantine.
    """

    def __init__(self, cfg: BingoConfig,
                 policy: GuardPolicy = GuardPolicy()):
        self.cfg = cfg
        self.policy = policy
        self.classify = make_classifier(cfg, policy)
        self.quarantine: List[QuarantineRecord] = []
        self.pending: Deque[PendingInsert] = deque()
        self.ingested = 0
        self.accepted = 0
        self.quarantined = 0
        self.retried = 0
        self.reason_counts = np.zeros(NUM_REASONS, np.int64)
        self.deletes_since_retry = 0
        self.regrows_since_retry = 0

    # -- conservation ------------------------------------------------------
    def check_conservation(self):
        """accepted + quarantined + pending == ingested, or raise."""
        total = self.accepted + self.quarantined + len(self.pending)
        if total != self.ingested:
            raise AssertionError(
                f"guard conservation broken: accepted={self.accepted} + "
                f"quarantined={self.quarantined} + "
                f"pending={len(self.pending)} != ingested={self.ingested}")

    def snapshot(self) -> dict:
        """JSON-able guard state for checkpoint manifests."""
        return {
            "ingested": self.ingested, "accepted": self.accepted,
            "quarantined": self.quarantined, "retried": self.retried,
            "deletes_since_retry": self.deletes_since_retry,
            "regrows_since_retry": self.regrows_since_retry,
            "reason_counts": self.reason_counts.tolist(),
            "quarantine": [list(q) for q in self.quarantine],
            "pending": [list(p) for p in self.pending],
        }

    def load_snapshot(self, snap: dict):
        self.ingested = int(snap["ingested"])
        self.accepted = int(snap["accepted"])
        self.quarantined = int(snap["quarantined"])
        self.retried = int(snap["retried"])
        self.deletes_since_retry = int(snap["deletes_since_retry"])
        self.regrows_since_retry = int(snap.get("regrows_since_retry", 0))
        self.reason_counts = np.asarray(snap["reason_counts"], np.int64)
        self.quarantine = [
            QuarantineRecord(int(r), bool(i), int(u), int(v), float(w),
                             int(c))
            for r, i, u, v, w, c in snap["quarantine"]]
        self.pending = deque(
            PendingInsert(int(r), int(u), int(v), float(w), int(n))
            for r, u, v, w, n in snap["pending"])

    # -- main-round accounting --------------------------------------------
    def account(self, rnd, is_insert, u, v, w, reasons_np) -> np.ndarray:
        """Route one classified round; returns the per-reason counts.

        OK lanes count as accepted (the caller applies them with
        ``active = reasons == R_OK``); ``R_CAPACITY`` insert lanes spill
        to the pending queue (quarantine when ``max_retries == 0``);
        everything else is quarantined.
        """
        is_insert = np.asarray(is_insert)
        u, v, w = np.asarray(u), np.asarray(v), np.asarray(w)
        counts = np.bincount(reasons_np, minlength=NUM_REASONS)
        counts[R_OK] = 0
        self.ingested += int(reasons_np.shape[0])
        self.accepted += int(np.sum(reasons_np == R_OK))
        self.reason_counts += counts
        for i in np.nonzero(reasons_np != R_OK)[0]:
            code = int(reasons_np[i])
            if code == R_CAPACITY and self.policy.max_retries > 0:
                self.pending.append(PendingInsert(
                    rnd, int(u[i]), int(v[i]), float(w[i]),
                    self.policy.max_retries))
            else:
                self.quarantine.append(QuarantineRecord(
                    rnd, bool(is_insert[i]), int(u[i]), int(v[i]),
                    float(w[i]), code))
                self.quarantined += 1
        return counts

    # -- capacity regrowth -------------------------------------------------
    def regrow(self, cfg_next: BingoConfig):
        """Re-target the guard at a grown capacity tier (DESIGN.md §14).

        The classifier's capacity check is against ``cfg.capacity``, so
        it must be rebuilt at the new tier; every pending insert gets
        its retry budget restored — exhausting the budget at the *old*
        tier says nothing about fitting at the new one.
        """
        self.cfg = cfg_next
        self.classify = make_classifier(cfg_next, self.policy)
        self.regrows_since_retry += 1
        self.pending = deque(
            p._replace(retries_left=self.policy.max_retries)
            for p in self.pending)

    # -- overflow retries --------------------------------------------------
    def want_retry(self) -> bool:
        # Retry once capacity may have been freed (deletes) *or* created
        # (a ladder regrow).  Requiring deletes alone starves insert-only
        # streams: a spilled insert would sit pending forever even after
        # the vertex's tier grew past its degree.
        return bool(self.pending) and (self.deletes_since_retry > 0
                                       or self.regrows_since_retry > 0)

    def take_retry(self):
        """Pop up to ``retry_batch`` pending inserts; pad to fixed shape.

        Returns ``(entries, u, v, w)`` — entries is the popped list (its
        length is the live lane count), arrays are ``(retry_batch,)``
        with pad lanes ``u = -1`` (classified ``R_VERTEX``, never
        applied, never accounted).
        """
        R = self.policy.retry_batch
        entries = [self.pending.popleft()
                   for _ in range(min(R, len(self.pending)))]
        u = np.full(R, -1, np.int32)
        v = np.zeros(R, np.int32)
        w = np.ones(R, np.float32 if self.cfg.fp_bias else np.int32)
        for i, p in enumerate(entries):
            u[i], v[i], w[i] = p.u, p.v, p.w
        self.deletes_since_retry = 0
        self.regrows_since_retry = 0
        return entries, u, v, w

    def settle_retry(self, rnd, entries, reasons_np) -> int:
        """Route retried lanes; returns how many applied."""
        applied = 0
        for i, p in enumerate(entries):
            code = int(reasons_np[i])
            if code != R_OK:
                self.reason_counts[code] += 1
            if code == R_OK:
                self.accepted += 1
                self.retried += 1
                applied += 1
            elif code == R_CAPACITY and p.retries_left > 1:
                self.pending.append(p._replace(retries_left=p.retries_left - 1))
            else:
                # out of retries — or the state changed under the entry
                # (e.g. its vertex became full of duplicates); quarantine
                # with the final reason, R_CAPACITY for exhausted budgets.
                self.quarantine.append(QuarantineRecord(
                    rnd, True, p.u, p.v, p.w, code))
                self.quarantined += 1
        return applied

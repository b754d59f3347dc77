"""ReplicaRouter: data-parallel scheduler pools behind ONE shared queue.

The paper's closing argument is that generative-AI inference "for billions
of users" is won at the serving layer: once one pool is compute-tight
(continuous batching, paged KV, chunked prefill, speculative windows —
PRs 2-7), the next lever is horizontal — N independent replicas of the
whole pool, each with its own KV cache on its own accelerator, fed from
one queue (ROADMAP "Multi-host serve", step 1). This module is that
step's scaffolding: the router owns N ``Scheduler`` pools (contiguous or
paged, chunked or not — replicas share ONE geometry and ONE set of
compiled executables) and does load-aware placement over them.

Placement policy, in order:

- **route by free capacity** — each arrived request is offered to the
  replica with the most free blocks (paged; Fig 1's binding resource) or
  free slots (contiguous), lowest replica id breaking ties;
- **spill on back-pressure** — if the top-choice replica refuses
  (``try_admit`` gate: no free slot, or blocks under the admission
  watermark), the request spills down the capacity ordering to the first
  replica that takes it (``n_spills`` counts these);
- **head-of-line blocking preserved** — if NO replica can take the
  highest-priority arrived candidate, placement stops (nobody may jump
  a class above theirs), exactly matching single-pool semantics;
- **requeue-front on replica-level preemption** — a replica that runs
  out of blocks preempts its youngest resident onto its own queue; after
  every round the router reclaims those (``drain_waiting``) onto the
  SHARED queue's front, so the replay may land on ANY replica.

The determinism spine: every committed token is sampled under a pure
per-(rid, stream, token-index) key folded from the router-wide shared
``base_key``, so a request's tokens are bit-identical regardless of which
replica serves it, which batch mates it decodes with, how often it is
preempted, or where the replay lands — routing is a pure scheduling
decision. ``tests/test_router.py`` locks this down against single-pool
serving under 1/2/3 replicas, both pool kinds, both temperatures, and
mid-decode preemption.

Stepping is pipelined through the scheduler's two-phase split: each
round dispatches ``step_begin`` on EVERY live replica before calling any
``step_finish`` — JAX's async dispatch then overlaps replica compute
when replicas sit on different devices (``distributed.sharding
.replica_devices`` pins each replica's params + cache to its own device;
on a single-device host they time-share it). Throughput accounting uses
each replica's ``busy_s`` (host wall time of its admissions and steps):
``total tokens / max-over-replicas busy_s`` is the fleet's aggregate
service rate — the wall a real one-device-per-replica deployment would
take — and is what ``bench_serve --replicas`` gates near-linear scaling
on, alongside the deterministic per-replica step-count balance.
"""
from __future__ import annotations

import time
from collections import deque
from typing import Any, Deque, Dict, List, Optional, Sequence

import jax

from repro.analysis.hotpath import hot_path
from repro.core.scheduler import Scheduler, ServeRequest, age_waiting
from repro.distributed import sharding


class ReplicaRouter:
    """N data-parallel ``Scheduler`` pools behind one shared queue.

    ``devices="auto"`` pins replica ``i``'s params + cache to
    ``jax.devices()[i % n_devices]`` when the host has more than one
    device, and leaves placement alone (shared default device, shared
    params object) otherwise. All replicas share ``base_key`` — the
    cross-replica determinism invariant depends on it.

    ``tp > 1`` is the DP x TP fleet story: each replica becomes a
    tensor-parallel pool (``Scheduler(tp_mesh=...)``) over its own
    DISJOINT device group — ``sharding.replica_devices(group_size=tp)``
    carves the host's devices into whole submeshes, so two replicas can
    never partially overlap. ``devices`` may then be an explicit list of
    device tuples (one ``tp``-sized group per replica).
    """

    def __init__(
        self,
        model,
        params,
        *,
        replicas: int,
        slots: int,
        pad_to: int,
        max_new_cap: int,
        eos_id: Optional[int] = None,
        paged: bool = False,
        block_size: int = 16,
        num_blocks: Optional[int] = None,
        chunked: bool = False,
        prefill_budget: Optional[int] = None,
        prefix_cache: bool = False,
        priority_boost_after: Optional[float] = None,
        base_key: Optional[jax.Array] = None,
        clock=time.perf_counter,
        devices: Any = "auto",
        tp: Optional[int] = None,
    ):
        if replicas < 1:
            raise ValueError(f"need at least one replica, got {replicas}")
        if base_key is None:
            base_key = jax.random.PRNGKey(0)
        self.tp = tp if tp is not None and tp > 1 else None
        if isinstance(devices, str) and devices == "auto":
            if self.tp is not None:
                # whole disjoint groups; raises when the host can't form
                # even one tp-sized submesh
                devices = sharding.replica_devices(
                    replicas, group_size=self.tp
                )
            else:
                devices = (
                    sharding.replica_devices(replicas)
                    if len(jax.devices()) > 1 else [None] * replicas
                )
        if len(devices) != replicas:
            raise ValueError(
                f"{replicas} replicas need {replicas} device pins, "
                f"got {len(devices)}"
            )
        self.clock = clock
        # SLA aging happens at the SHARED queue (the replicas' own queues
        # only ever hold preemption replays), so the router owns the knob
        self.priority_boost_after = priority_boost_after
        self.n_priority_boosts = 0
        if self.tp is not None:
            from repro.distributed import tp_pool

            meshes = [
                tp_pool.make_tp_mesh(self.tp, devices=group)
                for group in devices
            ]
            # the scheduler's TPContext commits params to each submesh
            # itself (sharded placement, not a whole-device pin)
            placements = [
                dict(device=None, tp_mesh=mesh) for mesh in meshes
            ]
            placed_params = [params] * replicas
        else:
            placements = [dict(device=dev) for dev in devices]
            placed_params = [
                sharding.place_replica(params, dev) for dev in devices
            ]
        self.replicas: List[Scheduler] = [
            Scheduler(
                model, placed_params[i],
                slots=slots, pad_to=pad_to, max_new_cap=max_new_cap,
                eos_id=eos_id, paged=paged, block_size=block_size,
                num_blocks=num_blocks, chunked=chunked,
                prefill_budget=prefill_budget,
                # each replica keeps its own INDEPENDENT trie: cached
                # blocks live in that replica's device pool, and hits are
                # bit-identical to cold prefill, so per-replica hit-rate
                # variance never leaks into tokens
                prefix_cache=prefix_cache,
                base_key=base_key,  # SHARED: tokens must not depend on placement
                clock=clock, replica_id=i, **placements[i],
            )
            for i in range(replicas)
        ]
        self.waiting: Deque[ServeRequest] = deque()
        self.finished: List[ServeRequest] = []
        # rid -> every replica id that admitted it (re-admissions after a
        # preemption append; the LAST entry is where it finished)
        self.placements: Dict[int, List[int]] = {}
        self.n_routed = 0  # admissions (>= n_requests with preemptions)
        self.n_spills = 0  # admitted by a non-top-choice replica
        self.n_requeues = 0  # preempted requests bounced to the shared queue
        self._t0 = self.clock()

    def _now(self) -> float:
        return self.clock() - self._t0

    # ---- shared queue ----------------------------------------------------
    def submit(self, requests: List[ServeRequest]) -> None:
        """Normalize + enqueue onto the SHARED queue (arrival order,
        higher priority first within an arrival instant — the same order
        one pool would see). Replicas share one geometry, so replica 0's
        ``normalize`` validates for the whole fleet."""
        for r in sorted(requests, key=lambda r: (r.t_arrival, -r.priority)):
            self.waiting.append(self.replicas[0].normalize(r))

    def _next_candidate(self, now: float):
        """(index, request) of the highest-priority ARRIVED request —
        same selection rule as ``Scheduler._next_candidate`` so routed
        admission order matches single-pool admission order."""
        best_i, best = None, None
        for i, r in enumerate(self.waiting):
            if r.t_arrival > now:
                break
            if best is None or r.priority > best.priority:
                best_i, best = i, r
        return best_i, best

    # ---- placement -------------------------------------------------------
    def _ranked(self) -> List[int]:
        """Replica ids, most free capacity first (free blocks when paged,
        free slots otherwise), lowest id breaking ties."""
        return sorted(
            range(len(self.replicas)),
            key=lambda i: (-self.replicas[i].free_capacity(), i),
        )

    def _place(self, now: float) -> None:
        """Admit arrived requests until the queue drains or the
        highest-priority candidate fits on NO replica (head-of-line
        blocking — matching single-pool semantics, and guaranteeing no
        admission stall while any replica can admit the candidate). Must
        not run between a round's ``step_begin`` and ``step_finish``: the
        commit walks the active set the dispatch captured."""
        self.n_priority_boosts += age_waiting(
            self.waiting, now, self.priority_boost_after
        )
        while True:
            i, cand = self._next_candidate(now)
            if cand is None:
                return
            placed = None
            for rank, rep in enumerate(self._ranked()):
                if self.replicas[rep].try_admit(cand, now):
                    placed = (rank, rep)
                    break
            if placed is None:
                return  # back-pressure everywhere; a step must free room
            del self.waiting[i]
            rank, rep = placed
            self.n_routed += 1
            if rank > 0:
                self.n_spills += 1
            self.placements.setdefault(cand.rid, []).append(rep)

    def _reclaim(self, sched: Scheduler) -> None:
        """Requeue-front on replica-level preemption: pull the requests a
        replica preempted onto ITS queue back onto the SHARED queue's
        front, order preserved, so the replay may land on any replica."""
        pre = sched.drain_waiting()
        for req in reversed(pre):
            self.waiting.appendleft(req)
        self.n_requeues += len(pre)

    def _harvest(self) -> None:
        for sched in self.replicas:
            self.finished.extend(sched.drain_finished())

    # ---- stepping --------------------------------------------------------
    @hot_path
    def _round(self, live: Sequence[Scheduler]) -> None:
        """One fleet round: dispatch every live replica's step, THEN sync
        and commit each — the cross-replica pipelining the two-phase step
        split exists for. No host sync happens until every replica's
        device work is in flight."""
        pendings = [(s, s.step_begin()) for s in live]
        for s, pending in pendings:
            s.step_finish(pending)

    def run(self, requests: List[ServeRequest]) -> List[ServeRequest]:
        """Serve ``requests`` across the fleet; returns them in harvest
        order. One shared clock origin keeps merged TTFT/TPOT timestamps
        comparable across replicas."""
        self.submit(requests)
        self._t0 = self.clock()
        for s in self.replicas:
            s.rebase(self._t0)
        while self.waiting or any(s.has_work for s in self.replicas):
            self._place(self._now())
            live = [s for s in self.replicas if s.has_work]
            if not live:
                if self.waiting:  # fleet idle, next arrival in the future
                    wait = self.waiting[0].t_arrival - self._now()
                    if wait > 0:
                        time.sleep(min(wait, 1e-3))
                continue
            self._round(live)
            for s in live:
                if s.waiting:
                    self._reclaim(s)
            self._harvest()
        return self.finished

    # ---- merged metrics --------------------------------------------------
    @property
    def n_decode_steps(self) -> int:
        return sum(s.n_decode_steps for s in self.replicas)

    @property
    def n_prefills(self) -> int:
        return sum(s.n_prefills for s in self.replicas)

    @property
    def n_preemptions(self) -> int:
        return sum(s.n_preemptions for s in self.replicas)

    @property
    def n_mixed_steps(self) -> int:
        return sum(s.n_mixed_steps for s in self.replicas)

    @property
    def reserved_bytes(self) -> int:
        return sum(s.pool.reserved_bytes for s in self.replicas)

    @property
    def n_prefix_lookups(self) -> int:
        return sum(s.n_prefix_lookups for s in self.replicas)

    @property
    def n_prefix_hits(self) -> int:
        return sum(s.n_prefix_hits for s in self.replicas)

    @property
    def n_prefix_tokens_skipped(self) -> int:
        return sum(s.n_prefix_tokens_skipped for s in self.replicas)

    @property
    def prefix_hit_rate(self) -> float:
        return self.n_prefix_hits / max(self.n_prefix_lookups, 1)

    @property
    def n_prefix_reclaimed(self) -> int:
        return sum(s.n_prefix_reclaimed for s in self.replicas)

    @property
    def mean_cached_blocks(self) -> float:
        per = [s.mean_cached_blocks for s in self.replicas]
        return float(sum(per) / len(per)) if per else 0.0

    @property
    def mean_occupancy(self) -> float:
        occ = [s.mean_occupancy for s in self.replicas if s.occupancy_trace]
        return float(sum(occ) / len(occ)) if occ else 0.0

    @property
    def admission_stalls(self) -> List[float]:
        out: List[float] = []
        for s in self.replicas:
            out.extend(s.admission_stalls)
        return out

    @property
    def busy_max_s(self) -> float:
        """The fleet's emulated wall: replicas run concurrently on real
        (one-device-per-replica) deployments, so the slowest replica's
        host wall time of its admissions and steps bounds the fleet's
        finish time."""
        return max(s.busy_s for s in self.replicas)

    @property
    def steps_max(self) -> int:
        """Deterministic balance twin of ``busy_max_s``: the slowest
        replica's pool-step count (all replicas replay the same compiled
        executables, so steps are cost-comparable across replicas)."""
        return max(s.n_decode_steps for s in self.replicas)

    def replica_report(
        self, done: Optional[List[ServeRequest]] = None
    ) -> List[Dict[str, Any]]:
        """Per-replica occupancy / step / preemption / busy-time summary.
        Requests attribute to the replica that FINISHED them (the last
        placement); pass the run's ``done`` list to add per-replica
        TTFT/TPOT percentiles and busy-time service rate."""
        import numpy as np

        by_rep: Dict[int, List[ServeRequest]] = {
            i: [] for i in range(len(self.replicas))
        }
        for r in (done or []):
            path = self.placements.get(r.rid)
            if path:
                by_rep[path[-1]].append(r)
        served: Dict[int, int] = {i: 0 for i in range(len(self.replicas))}
        for rid, path in self.placements.items():
            served[path[-1]] += 1
        out = []
        for s in self.replicas:
            e: Dict[str, Any] = {
                "replica": s.replica_id,
                "device": str(s.device) if s.device is not None else None,
                "n_requests": served[s.replica_id],
                "decode_steps": s.n_decode_steps,
                "prefills": s.n_prefills,
                "preemptions": s.n_preemptions,
                "busy_s": s.busy_s,
                "mean_slot_occupancy": s.mean_occupancy,
            }
            if done is not None:
                rs = by_rep[s.replica_id]
                ttft = [r.ttft for r in rs]
                tpot = [r.tpot for r in rs if len(r.tokens) > 1]
                e.update(
                    ttft_p50_ms=(
                        float(np.percentile(ttft, 50)) * 1e3 if ttft else 0.0
                    ),
                    ttft_p99_ms=(
                        float(np.percentile(ttft, 99)) * 1e3 if ttft else 0.0
                    ),
                    tpot_p50_ms=(
                        float(np.percentile(tpot, 50)) * 1e3 if tpot else 0.0
                    ),
                    tokens_per_s_busy=(
                        sum(len(r.tokens) for r in rs) / max(s.busy_s, 1e-9)
                    ),
                )
            out.append(e)
        return out

"""Hot-path marking for the HS001 host-sync lint rule.

A *hot-path* function is one that runs per decode step while requests
are resident — the code where a stray host sync (``np.asarray``,
``.item()``, a ``bool()`` cast on a device value) re-opens the idle
bubbles the paper's Obs #2 measures. Two ways to mark one:

- decorate it with :func:`hot_path` (a runtime no-op; the AST lint
  detects the decorator statically), or
- list its dotted name in :data:`HOT_PATHS` — for functions whose
  modules should not import this package, or to mark third-party-shaped
  seams without touching their source.

Admission / finish bookkeeping (``_admit_one`` & co.) is deliberately
NOT hot: it runs once per request, already contains a prefill program,
and its `int()` casts are request-lifecycle work — the per-TOKEN loop
is what the rule protects.

This module must stay dependency-free: core serving modules import the
decorator, and they must never pull the analysis machinery (ast/json)
into the serving process.
"""
from __future__ import annotations

from typing import Callable, TypeVar

F = TypeVar("F", bound=Callable)

#: Dotted names (``module.qualname``) treated as hot without a
#: decorator. Kept for seams where decorating is impossible or
#: undesirable; prefer ``@hot_path`` where the source is ours.
HOT_PATHS = frozenset({
    # the pool-wide per-token device programs (jit-decorated, so the
    # registry marks them instead of stacking a second decorator on the
    # PjitFunction object)
    "repro.core.engine.decode_step",
    "repro.core.engine.mixed_step",
    # the speculative step pair: drafts + multi-token verification run
    # once per pool step while a SpeculativeProfile request is resident
    "repro.core.engine.verify_step",
    "repro.core.layerskip.draft_window",
    # the tensor-parallel step family (distributed/tp_pool.py): the same
    # per-token programs lowered onto a ("model",) mesh — one sharded
    # executable each, replayed exactly like their single-device twins
    "repro.core.engine.tp_prefill",
    "repro.core.engine.tp_decode_step",
    "repro.core.engine.tp_mixed_step",
    "repro.core.engine.tp_verify_step",
    "repro.core.layerskip.tp_draft_window",
    # the cross-request prefix cache's trie walks run once per admission
    # (match/insert) and inside the out-of-blocks back-pressure path
    # (reclaim) — pure host code, but on the admission hot path, so HS001
    # guards them against per-token host syncs/casts (the trie keys are
    # raw span BYTES for exactly this reason). The scheduler-side hooks
    # (_prefix_admit, _ensure_or_reclaim) carry @hot_path directly.
    "repro.core.prefix_cache.PrefixCache.match",
    "repro.core.prefix_cache.PrefixCache.insert",
    "repro.core.prefix_cache.PrefixCache.reclaim",
    # replica routing (core/router.py) adds NO new device programs: every
    # replica replays the executables above (one shared jit cache keyed by
    # pool geometry). Its per-round host code IS hot, and is decorated
    # directly: ReplicaRouter._round and the scheduler's two-phase
    # step_begin/step_finish split it drives.
    # the lane-bank group step: one program a pool step advances every
    # resident slot group of one decoding profile
    "repro.core.profiles.lane_step",
})


def hot_path(fn: F) -> F:
    """Mark ``fn`` as serving-hot-path for the AST lint (HS001). Runtime
    no-op: returns ``fn`` unchanged (no wrapper — jit caches, bound
    methods and reprs all see the original function)."""
    fn.__repro_hot_path__ = True  # introspectable, e.g. for tests
    return fn

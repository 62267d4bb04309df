"""Pipelined chunk-streaming execution (the `MASTIC_PIPELINE` lever).

The chunked production path (PERF.md §4-5) streams fixed-size report
chunks through one compiled round program.  Serially, each chunk pays
the full upload -> compute -> download -> host chain with blocking
`np.asarray` walls between every step, so the device idles during
host work and the host idles during device work — the r5 chip
session's `incremental_round` measured the production round at 211k
evals/s on a chip whose kernel runs at 43.4M evals/s, with 100.8 s of
inline XLA compile sitting on the critical path.  This module attacks both
gaps:

* **double-buffered chunk streaming** (`run_chunks`): chunk i+1's
  batch and carries upload and its round dispatches while chunk i
  computes and downloads, leaning on JAX async dispatch — the
  accept/ok/weight-check masks stay device arrays until one blocking
  sync per chunk, issued only after the next chunk's work is already
  in flight.  The per-chunk phase timeline (upload / dispatch /
  compute-wait / download / host) is recorded so overlap efficiency
  is a measured number in `RoundMetrics.extra`, not a claim;

* **ahead-of-time bucket compilation** (`ProgramCache` +
  `predicted_next_plans`): the round programs specialize on the
  power-of-two binder buckets and padded width of the live frontier
  (`backend/incremental.RoundPlan`), all host-predictable from the
  frontier trajectory — the predicted next `(bucket, width)`
  programs compile while the current round's dispatched device work
  is still executing (async dispatch keeps the device busy through
  the compile), moving the compile stalls off the critical path.
  This composes with the persistent `jax_compilation_cache_dir`
  (which only helps the *second* process): warming makes the *first*
  process's later rounds compile-free too.  (See ProgramCache for
  why warming is synchronous-overlapped rather than a compiler
  thread: concurrent tracing is unsound on this jax.)

Memory honesty lives in `drivers/chunked.py`: two chunks in flight
double the resident chunk state, so `memory_envelope` reports the
pipelined footprint and the runner degrades to serial (naming the
fallback in metrics) when the doubled footprint would exceed
`MASTIC_DEVICE_BUDGET_BYTES`.
"""

import gc
import os
import time
from contextlib import contextmanager
from typing import Callable, Optional, Sequence

import jax

from ..obs import trace as obs_trace


@contextmanager
def paused_gc():
    """Generational GC paused around a trace/compile window.

    A collection firing MID-TRACE segfaults this jax/jaxlib build —
    observed repeatedly via faulthandler ("Garbage-collecting" inside
    pjit tracing / abstract eval), single-threaded, with no
    persistent cache involved; the trigger is tracing while earlier
    runs' jit graphs sit collectable.  Deferring collection past the
    trace is semantically free: the next allocation after re-enabling
    collects outside the danger window.  Nested uses are fine (inner
    exit leaves GC disabled until the outer exit)."""
    if not gc.isenabled():
        yield
        return
    gc.disable()
    try:
        yield
    finally:
        gc.enable()


def pipeline_enabled() -> bool:
    """The `MASTIC_PIPELINE` lever, read per round (not at import) so
    a long-lived process can be steered without restarting.  Default
    on: the pipelined path is bit-identical to serial (locked by
    tests/test_pipeline.py) and strictly reduces wall clock."""
    return os.environ.get("MASTIC_PIPELINE", "1").lower() \
        not in ("0", "off", "false", "")


# -- double-buffered executor -----------------------------------------

def run_chunks(num_chunks: int, stage: Callable, collect: Callable,
               pipelined: bool,
               before_last_collect: Optional[Callable] = None) -> tuple:
    """Drive `stage`/`collect` over `num_chunks` chunks.

    `stage(i) -> (handle, phases)` uploads chunk i's inputs and
    dispatches its device work WITHOUT blocking on results (JAX async
    dispatch returns futures); `collect(i, handle) -> phases` issues
    the chunk's single blocking sync, downloads its results and folds
    them into host state.  `phases` are dicts of phase-name -> ms.

    Pipelined mode keeps two chunks in flight: chunk i+1 stages while
    chunk i's results are still being computed/collected.  Serial
    mode collects each chunk before staging the next (the shape of
    the pre-pipeline loop — the comparison baseline and the memory
    fallback).

    `before_last_collect` runs after every chunk's work is dispatched
    and before the final blocking collect — the point where the
    device is maximally busy and the host is about to idle.  The
    runners hang the ahead-of-time compile of the predicted next
    round's programs here, so XLA work overlaps in-flight device
    execution instead of sitting between a round's dispatch and its
    results.

    Returns (timeline, wall_ms): per-chunk records with absolute
    stage/collect timestamps (ms since round start) and the merged
    phase dict, plus the loop's total wall clock.  Timestamps let
    tests assert real overlap structurally: pipelined execution has
    timeline[i+1]["stage_start_ms"] < timeline[i]["collect_start_ms"].
    """
    timeline: list = [None] * num_chunks
    t0 = time.perf_counter()

    def now_ms() -> float:
        return (time.perf_counter() - t0) * 1e3

    tracer = obs_trace.get_tracer()

    def do_stage(i: int):
        start = now_ms()
        # The chunk spans nest under the caller's "round" span, so a
        # trace reconstructs round -> chunk (ISSUE 7).
        with tracer.span("chunk.stage", chunk=i):
            (handle, phases) = stage(i)
        timeline[i] = {
            "chunk": i,
            "stage_start_ms": round(start, 3),
            "stage_end_ms": round(now_ms(), 3),
            "phases": dict(phases),
            "host_syncs": 0,
        }
        return handle

    def do_collect(i: int, handle) -> None:
        if i == num_chunks - 1 and before_last_collect is not None:
            before_last_collect()
        rec = timeline[i]
        rec["collect_start_ms"] = round(now_ms(), 3)
        with tracer.span("chunk.collect", chunk=i):
            rec["phases"].update(collect(i, handle))
        rec["collect_end_ms"] = round(now_ms(), 3)
        # collect() blocks exactly once (jax.block_until_ready on the
        # chunk's full output tree); everything after is ready-data
        # copies.  Recorded so the one-sync contract is testable.
        rec["host_syncs"] = 1

    if pipelined and num_chunks > 1:
        in_flight = do_stage(0)
        for i in range(num_chunks):
            staged_next = (do_stage(i + 1) if i + 1 < num_chunks
                           else None)
            do_collect(i, in_flight)
            in_flight = staged_next
    else:
        for i in range(num_chunks):
            do_collect(i, do_stage(i))
    return (timeline, now_ms())


def overlap_efficiency(timeline: Sequence[dict],
                       wall_ms: float) -> float:
    """Fraction of the chunks' total phase time hidden by overlap:
    1 - wall / sum(phases).  0.0 when nothing overlapped (serial, or
    a single chunk); approaches the ideal (n-1)/n stacking as upload
    and download fully hide under compute."""
    busy = sum(sum(rec["phases"].values()) for rec in timeline)
    if wall_ms <= 0.0 or busy <= wall_ms:
        return 0.0
    return round(1.0 - wall_ms / busy, 4)


# -- shape-keyed compiled-program cache + background warming ----------

def to_struct(x) -> jax.ShapeDtypeStruct:
    """Array -> abstract shape/dtype (the lowering signature).

    Mesh-placed arrays keep their NamedSharding: a warm compile for a
    mesh-sharded round must lower with the same input shardings the
    real call will pass, or the cached executable would be rejected
    (or silently recompiled) at dispatch.  Single-device arrays stay
    sharding-free — pinning their SingleDeviceSharding would
    needlessly specialize the program to one device ordinal."""
    from jax.sharding import NamedSharding

    sharding = getattr(x, "sharding", None)
    if isinstance(sharding, NamedSharding):
        return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                    sharding=sharding)
    return jax.ShapeDtypeStruct(x.shape, x.dtype)


class ProgramCache:
    """Compiled round programs keyed by the shapes they actually
    close over (chunk rows, padded width, the pow2 binder/out
    buckets) plus the runtime tag (jax version + backend) and program
    family (instantiation + ctx digest) — NOT cleared on width
    growth: a grown runner simply compiles (or has pre-warmed) the
    new width's keys while the old entries become unreachable.  A key
    stamped for a different runtime is REFUSED (`artifacts.
    check_key_runtime`): an in-process cache can never serve a
    program compiled under a different jax build or backend.

    `get` is the inline path: returns the compiled program plus the
    seconds the caller had to WAIT for it — zero exactly when a warm
    already landed it, the full compile when cold (the timeline's
    compile field, so the zero-inline-compile claim is measured, not
    asserted).  `warm` compiles SYNCHRONOUSLY on the caller's thread:
    the runners invoke it only at points where every in-flight
    chunk's device work is already dispatched and the host is about
    to idle in a blocking sync (run_chunks' `before_last_collect`
    hook), so the XLA work overlaps device execution.  A separate
    compiler thread is deliberately NOT used: jax tracing is not
    thread-safe on this fabric (0.4.x) — a background thread lowering
    while the main thread traced produced both hard crashes
    (segfault/std::terminate) and, worse, silently WRONG jaxprs
    (observed: a round program that rejected every report).  The
    synchronous form keeps the same measured win — dispatch is async,
    so the device computes through the compile — with none of the
    failure modes, and it composes with the persistent
    `jax_compilation_cache_dir` across processes.

    `store` plugs in the AOT artifact tier (`drivers/artifacts.py`,
    ROADMAP item 4): below the in-process dict, a cache miss consults
    the digest-sealed, probe-verified on-disk store before paying
    XLA — `get` loads inline (the wait is the disk+probe latency,
    ~1.5 s vs ~21 s compile on this fabric), `warm` prefetches from
    disk in the same overlapped slot it would have compiled in, and
    `preload` walks the store up front so first rounds hit the
    in-process tier directly.  Artifact loads are never counted as
    inline compiles — the `artifact_hits` / `artifact_load_ms` stats
    attribute them separately.
    """

    def __init__(self, store=None):
        self._programs: dict = {}
        self.store = store
        self.stats = {"inline_compiles": 0, "warm_compiles": 0,
                      "warm_errors": 0, "artifact_hits": 0,
                      "artifact_load_ms": 0.0}

    def _check_runtime(self, key) -> None:
        from .artifacts import check_key_runtime

        check_key_runtime(key)

    def _from_store(self, key):
        """Artifact-tier lookup: gated load (digest / runtime / probe
        — see artifacts.ArtifactStore.load), memoized into the
        in-process tier on success."""
        if self.store is None:
            return None
        t0 = time.perf_counter()
        prog = self.store.load(key)
        if prog is None:
            return None
        self._programs[key] = prog
        self.stats["artifact_hits"] += 1
        self.stats["artifact_load_ms"] += \
            (time.perf_counter() - t0) * 1e3
        return prog

    def get(self, key, build: Callable) -> tuple:
        """(compiled, wait_seconds); `build()` returns a Lowered."""
        self._check_runtime(key)
        prog = self._programs.get(key)
        if prog is not None:
            return (prog, 0.0)
        t0 = time.perf_counter()
        prog = self._from_store(key)
        if prog is not None:
            return (prog, time.perf_counter() - t0)
        with paused_gc():
            compiled = build().compile()
        self._programs[key] = compiled
        self.stats["inline_compiles"] += 1
        return (compiled, time.perf_counter() - t0)

    def warm(self, key, build: Callable) -> float:
        """Land `key` now if absent — from the artifact store when it
        has the key (the predictor prefetches from disk before
        compiling), else by compiling; returns the seconds spent.
        Errors are counted, never raised: a mispredicted or
        unbuildable warm must not take down the round that scheduled
        it — the real round compiles inline instead."""
        self._check_runtime(key)
        if key in self._programs:
            return 0.0
        t0 = time.perf_counter()
        if self._from_store(key) is not None:
            return time.perf_counter() - t0
        try:
            with paused_gc():
                self._programs[key] = build().compile()
            self.stats["warm_compiles"] += 1
        except Exception:
            self.stats["warm_errors"] += 1
        return time.perf_counter() - t0

    def preload(self, match: Callable) -> int:
        """Pull every store entry whose key passes `match` into the
        in-process tier (runner construction calls this with its
        shape family, so the first round's `get` is a pure dict
        hit and the timeline's compile field stays zero)."""
        if self.store is None:
            return 0
        n = 0
        for key in self.store.keys():
            if key in self._programs or not match(key):
                continue
            if self._from_store(key) is not None:
                n += 1
        return n

    def entries(self) -> dict:
        """The compiled programs by key (bake-from-run export)."""
        return dict(self._programs)

    def contains(self, key) -> bool:
        return key in self._programs


# -- frontier-trajectory bucket prediction ----------------------------

def plan_shape_key(plan) -> tuple:
    """The shapes a RoundPlan's traced inputs specialize the compiled
    round program on: padded width plus the pow2 onehot / payload /
    out buckets.  (`level` et al. are traced scalars — free.)"""
    return (plan.width, len(plan.onehot_idx),
            len(plan.payload_parent), len(plan.out_idx))


def _candidate_survivor_sets(prefixes: Sequence) -> list:
    """The two frontier trajectories worth warming for, derived from
    the current prefix set:

    * steady state — the threshold keeps ~one child per parent, the
      heavy-hitters fixed point (frontier width constant; which child
      survives does not matter for SHAPES: per-depth ancestor counts,
      and therefore every bucket, are identity-independent);
    * growth — every prefix survives (the early levels of a run, and
      any level where the threshold prunes nothing).

    Anything else (mass extinction, partial prune straddling a pow2
    boundary) mispredicts and pays its compile inline — correctness
    is untouched, only the stall location moves."""
    groups: dict = {}
    for p in prefixes:
        groups.setdefault(p[:-1], []).append(p)
    steady = tuple(children[0] for children in groups.values())
    return [tuple(prefixes), steady]


def predicted_next_plans(prefixes: Sequence, level: int, bits: int,
                         width: int, layouts_next: list) -> list:
    """Predicted RoundPlans for level+1, deduplicated by shape key.
    `layouts_next` must already include the current round's new
    layout (the depth the in-flight round is creating).  Candidates
    that would force a width growth are skipped — the grow round
    recompiles inline by design (at most log2(max_width) times per
    run)."""
    from ..backend.incremental import RoundPlan

    if level + 1 >= bits:
        return []
    plans = []
    seen = set()
    for survivors in _candidate_survivor_sets(list(prefixes)):
        nxt = tuple(p + (b,) for p in survivors
                    for b in (False, True))
        try:
            plan = RoundPlan(nxt, level + 1, bits, width, layouts_next)
        # a candidate that does not fit the padded width is not an
        # error — the grow round compiles inline by design, and the
        # miss is observable as aot.predicted=False in the metrics
        except ValueError:  # mastic-allow: RB002 — infeasible
            # prediction candidate skipped; recorded via aot stats
            continue
        key = plan_shape_key(plan)
        if key not in seen:
            seen.add(key)
            plans.append(plan)
    return plans

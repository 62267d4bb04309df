"""Weighted heavy hitters: the multi-round collector loop.

Functionally equivalent to the reference driver
(/root/reference/poc/examples.py:13-91) — per level, aggregate over the
candidate-prefix frontier, threshold-prune, expand survivors — but the
per-report prep loop is replaced by one batched device round per level
(both aggregators' prep + accept + aggregation on device; the FLP
verifier exchange on the weight-check round crosses the host boundary,
as it does between real aggregators).

Thresholds: a dict mapping prefix tuples to ints with a "default" key;
the threshold for a prefix is that of its *longest strict ancestor*
present in the dict, else the default (reference examples.py:26-34,
spec draft-mouris-cfrg-mastic.md:1535-1572).
"""

import time
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..common import gen_rand, vec_add
from ..mastic import Mastic, ReportRejected
from ..metrics import (RoundMetrics, attribute_rejections,
                       count_round_bytes, count_round_ops)
from ..obs import devtime, trace as obs_trace
from ..backend.mastic_jax import BatchedMastic, ReportBatch


def get_reports_from_measurements(mastic: Mastic, ctx: bytes,
                                  measurements: Sequence) -> list:
    """Client side: shard each measurement with fresh randomness."""
    reports = []
    for measurement in measurements:
        nonce = gen_rand(mastic.NONCE_SIZE)
        rand = gen_rand(mastic.RAND_SIZE)
        (public_share, input_shares) = mastic.shard(
            ctx, measurement, nonce, rand)
        reports.append((nonce, public_share, input_shares))
    return reports


def get_threshold(thresholds: dict, prefix: tuple) -> int:
    """Longest-strict-ancestor threshold lookup."""
    for level in reversed(range(len(prefix) - 1)):
        if prefix[:level + 1] in thresholds:
            return thresholds[prefix[:level + 1]]
    return thresholds["default"]


def _vk_array(verify_key: bytes) -> jax.Array:
    return jnp.asarray(np.frombuffer(verify_key, np.uint8))


def _round_fn(bm: BatchedMastic, ctx: bytes, agg_param):
    """The jitted full-round function, cached on the BatchedMastic so
    repeated rounds with the same aggregation parameter (or repeated
    aggregate_by_attribute calls) reuse the compiled program.

    The verify key is a TRACED input, not a baked constant: a fresh
    per-collection key must not recompile the round (it previously
    did — every fresh-key test run re-paid the full XLA compile)."""
    cache = getattr(bm, "_round_cache", None)
    if cache is None:
        cache = {}
        bm._round_cache = cache
    key = (ctx, agg_param)
    fn = cache.get(key)
    if fn is None:
        fn = jax.jit(lambda vk, b: bm.round_device_checks(
            vk, ctx, agg_param, b))
        cache[key] = fn
    return fn


# -- the from-root round through the AOT program tier (ISSUE 10) ------

def root_program_cache(bm: BatchedMastic):
    """The from-root round's ProgramCache, shared per BatchedMastic —
    the artifact tier the attribute-metrics round (and the
    incremental=False differential path) previously sat outside: its
    per-(ctx, agg_param) jits were bare, so every fresh process (and
    every service epoch, which builds a fresh run) re-paid the full
    trace+XLA bill even with a warm artifact store.  Keys ride the
    same runtime+family suffix as eval/agg/wc/rk, so `tools/bake.py
    --attributes` seals them and the service preload at tenant
    admission pulls them in."""
    cache = getattr(bm, "_root_program_cache", None)
    if cache is None:
        from . import artifacts
        from .pipeline import ProgramCache

        cache = ProgramCache(store=artifacts.store_from_env())
        bm._root_program_cache = cache
    return cache


def root_program_key(bm: BatchedMastic, ctx: bytes, agg_param,
                     rows: int, shards: int = 0) -> tuple:
    """Shape-and-parameter key for one from-root round program.  The
    candidate prefixes are BAKED into the traced program (they drive
    the gather schedule), so the key carries their digest — two
    attribute sets of equal size map to different keys, never to each
    other's executable."""
    import hashlib

    from . import artifacts

    (level, prefixes, do_weight_check) = agg_param
    packed = "|".join("".join("1" if b else "0" for b in p)
                      for p in prefixes).encode()
    digest = hashlib.sha256(packed).hexdigest()[:16]
    return ("root", rows, shards, level, int(do_weight_check),
            digest, artifacts.runtime_tag(),
            artifacts.family_id(bm, ctx))


def root_round_program(bm: BatchedMastic, ctx: bytes, agg_param,
                       args: tuple, mesh=None) -> tuple:
    """(program, wait_seconds) for a from-root round at the shapes of
    `args` — the in-process tier first, the digest-sealed artifact
    store below it, inline XLA last (attributed in the cache stats,
    surfaced per round in `extra["artifacts"]`)."""
    from .pipeline import to_struct

    if mesh is not None:
        from ..drivers.attribute_metrics import _round_fn_masked

        fn = _round_fn_masked(bm, ctx, agg_param, mesh)
        shards = mesh.shape["reports"]
    else:
        fn = _round_fn(bm, ctx, agg_param)
        shards = 0
    rows = int(args[1].nonces.shape[0])
    key = root_program_key(bm, ctx, agg_param, rows, shards)
    structs = jax.tree_util.tree_map(to_struct, args)
    return root_program_cache(bm).get(
        key, lambda: fn.lower(*structs))


def _artifacts_delta(cache, mark: dict) -> dict:
    """The per-round `extra["artifacts"]` block from a ProgramCache
    stats snapshot taken at round start (obs/schema.py shape)."""
    s = cache.stats
    return {
        "store": (cache.store.path if cache.store is not None
                  else None),
        "hits": s["artifact_hits"] - mark["artifact_hits"],
        "inline_compiles": (s["inline_compiles"]
                            - mark["inline_compiles"]),
        "load_ms": round(s["artifact_load_ms"]
                         - mark["artifact_load_ms"], 2),
    }


def run_round_stage(bm: BatchedMastic, verify_key: bytes, ctx: bytes,
                    agg_param, batch: ReportBatch) -> dict:
    """Dispatch one from-root round WITHOUT blocking: program fetch
    (AOT tier), async dispatch, futures into the handle.  The paired
    `run_round_collect` issues the round's single blocking sync — the
    seam the overlapped epoch executor interleaves across tenants
    (tenant B stages here while tenant A's dispatched round computes
    on device)."""
    from .pipeline import paused_gc

    cache = root_program_cache(bm)
    mark = dict(cache.stats)
    args = (_vk_array(verify_key), batch)
    with paused_gc():
        (prog, wait_s) = root_round_program(bm, ctx, agg_param, args)
        out = prog(*args)
    return {"out": out, "compile_wait_s": wait_s,
            "artifacts": _artifacts_delta(cache, mark)}


def run_round_collect(bm: BatchedMastic, verify_key: bytes,
                      ctx: bytes, agg_param, handle: dict,
                      reports: Optional[list] = None,
                      accept_out: Optional[list] = None,
                      metrics_out: Optional[list] = None) -> list:
    """The blocking half of `run_round_stage`: one sync, downloads,
    the scalar-fallback splice, metrics, unshard."""
    from ..backend.schedule import LevelSchedule

    (level, prefixes, _do_weight_check) = agg_param
    (agg0, agg1, accept, ok, checks) = handle["out"]
    jax.block_until_ready((agg0, agg1, accept, ok))
    accept = np.asarray(accept).copy()
    ok = np.asarray(ok)
    sched = LevelSchedule(prefixes, level, bm.m.vidpf.BITS)
    agg_shares = [bm.agg_share_to_host(a) for a in (agg0, agg1)]
    extra = {"artifacts": handle["artifacts"]}
    result = finalize_round(
        bm, verify_key, ctx, agg_param, reports, ok, accept,
        {k: np.asarray(v) for (k, v) in checks.items()}, agg_shares,
        padded_width=sched.total_nodes,
        nodes_evaluated=sched.total_nodes, metrics_out=metrics_out,
        extra=extra)
    if accept_out is not None:
        accept_out.append(accept)
    return result


def run_round(bm: BatchedMastic, verify_key: bytes, ctx: bytes,
              agg_param, batch: ReportBatch,
              reports: Optional[list] = None,
              accept_out: Optional[list] = None,
              metrics_out: Optional[list] = None) -> list:
    """One aggregation round on the batched backend: both preps, all
    checks (incl. the device FLP on weight-check rounds), masked
    aggregation, unshard.  Returns the per-prefix aggregate result;
    appends the accept mask to `accept_out` and a RoundMetrics record
    to `metrics_out`.

    `reports` is the host-side report list backing `batch`; it is only
    touched when XOF rejection sampling fires for some lane (the scalar
    fallback, see `splice_rejected`).  Since ISSUE 10 the round
    program rides the AOT artifact tier (`root_round_program`), and
    the round itself is the stage/collect pair the overlapped epoch
    executor splits."""
    handle = run_round_stage(bm, verify_key, ctx, agg_param, batch)
    return run_round_collect(bm, verify_key, ctx, agg_param, handle,
                             reports=reports, accept_out=accept_out,
                             metrics_out=metrics_out)


def finalize_round(bm: BatchedMastic, verify_key: bytes, ctx: bytes,
                   agg_param, reports: Optional[list],
                   ok: np.ndarray, accept: np.ndarray, checks: dict,
                   agg_shares: list, padded_width: int,
                   nodes_evaluated: int,
                   metrics_out: Optional[list],
                   extra: Optional[dict] = None) -> list:
    """Shared from-root round finalization (run_round and the chunked
    attribute-metrics round): metrics record with per-check rejection
    attribution, the XOF-rejection scalar-fallback splice, unshard.

    From-root rounds evaluate the whole child grid; the beta shares
    on weight-check rounds reuse the depth-0 children (contrast the
    reference, whose get_beta_share re-evaluates them,
    mastic.py:235-236)."""
    (level, prefixes, _do_weight_check) = agg_param
    num_reports = accept.shape[0]
    metrics = RoundMetrics(level=level, frontier_width=len(prefixes),
                           padded_width=padded_width,
                           reports_total=num_reports)
    attribute_rejections(metrics, checks["eval_proof"],
                         checks.get("weight_check"),
                         checks.get("joint_rand"), device_ok=ok)
    count_round_ops(metrics, bm.m, num_reports, nodes_evaluated,
                    include_key_setup=True)
    count_round_bytes(metrics, bm.m, agg_param, num_reports)
    metrics.xof_fallbacks = int((~ok).sum())
    if extra:
        metrics.extra.update(extra)

    splice_rejected(bm.m, verify_key, ctx, agg_param, reports,
                    ok, accept, agg_shares)
    metrics.accepted = int(accept.sum())
    metrics.rejected_fallback = int((~ok & ~accept).sum())
    if metrics_out is not None:
        metrics_out.append(metrics)
    return bm.m.unshard(agg_param, agg_shares, int(accept.sum()))


def scalar_round_out_shares(m: Mastic, verify_key: bytes, ctx: bytes,
                            agg_param, report) -> Optional[list]:
    """One report through the scalar protocol round (both preps, the
    prep-share exchange, prep_next).  Returns the two out shares, or
    None if the report is rejected by the checks.

    The scalar layer's XOF sampler implements the true rejection loop
    (vdaf-13 §6.2; reference consumption /root/reference/poc/
    vidpf.py:352-364), so this path is exact for the lanes the batched
    sampler flags."""
    (nonce, public_share, input_shares) = report
    states = []
    shares = []
    for agg_id in range(2):
        (state, share) = m.prep_init(verify_key, ctx, agg_id, agg_param,
                                     nonce, public_share,
                                     input_shares[agg_id])
        states.append(state)
        shares.append(share)
    try:
        prep_msg = m.prep_shares_to_prep(ctx, agg_param, shares)
        return [m.prep_next(ctx, state, prep_msg) for state in states]
    except ReportRejected:
        return None


def splice_rejected(m: Mastic, verify_key: bytes, ctx: bytes, agg_param,
                    reports: Optional[list], ok: np.ndarray,
                    accept: np.ndarray, agg_shares: list) -> None:
    """The XOF rejection-sampling fallback (vdaf-13 §6.2).

    Lanes where `ok` is False sampled a field element outside the
    field (~2^-32 per element for Field64): their device results are
    garbage, and the device aggregates already exclude them.  Recompute
    exactly those reports through the scalar layer and splice their
    out shares and accept bits into the round's host-side results
    (`accept` and `agg_shares` are mutated in place)."""
    if ok.all():
        return
    if reports is None:
        raise ValueError(
            "XOF rejection sampling fired but the host reports needed "
            "for the scalar fallback were not provided")
    for r in np.flatnonzero(~ok):
        out_shares = scalar_round_out_shares(m, verify_key, ctx,
                                             agg_param, reports[r])
        accept[r] = out_shares is not None
        if out_shares is not None:
            for a in range(2):
                agg_shares[a] = vec_add(agg_shares[a], out_shares[a])


def compute_heavy_hitters(mastic: Mastic, ctx: bytes, thresholds: dict,
                          reports: list,
                          verify_key: Optional[bytes] = None,
                          incremental: bool = True) -> list:
    """The full collector loop (reference examples.py:37-91).

    With `incremental` (the default), each aggregator carries its
    prefix-tree state across rounds and only evaluates the new level's
    frontier — O(BITS * frontier) node evaluations for the whole run
    instead of O(BITS^2 * frontier) — using one compiled round program
    per padded frontier width (backend/incremental.py).  The
    `incremental=False` path re-evaluates from the root each round
    (one compile per level) and serves as the differential reference.
    """
    run = HeavyHittersRun(mastic, ctx, thresholds, reports,
                          verify_key=verify_key,
                          incremental=incremental)
    while run.step():
        pass
    return run.result()


# v2 added the chunk_size meta field (0 = unchunked); v3 added the
# per-depth creation layouts (carries are no longer compacted per
# round, so the row arrangement can't be derived from the last
# aggregation parameter alone).  v1/v2 checkpoints hold compacted
# carries, whose arrangement IS needed_paths(last prefixes) — still
# restorable.
_CKPT_VERSION = 3


def _ckpt_binding(verify_key: bytes, ctx: bytes,
                  thresholds: dict) -> np.ndarray:
    """Digest binding a checkpoint to its (verify_key, ctx,
    thresholds): restoring under a different key/context would
    silently reject every report (the carries were derived under the
    old key), and different thresholds would prune a different
    frontier — make either mismatch loud instead."""
    import hashlib
    thresh_repr = repr(sorted(thresholds.items(), key=repr)).encode()
    digest = hashlib.sha256(
        len(verify_key).to_bytes(2, "little") + verify_key +
        len(ctx).to_bytes(2, "little") + ctx + thresh_repr
    ).digest()
    return np.frombuffer(digest, np.uint8)


def _paths_to_array(paths) -> np.ndarray:
    if not paths:
        return np.zeros((0, 0), bool)
    return np.array([[bool(b) for b in p] for p in paths], bool)


def _paths_from_array(arr) -> list:
    return [tuple(bool(x) for x in row) for row in np.asarray(arr)]


class HeavyHittersRun:
    """A resumable heavy-hitters collection run: one `step()` per tree
    level, checkpointable between levels (SURVEY.md §5; the state the
    reference would persist is named at examples.py:48,75 plus the
    cache-across-rounds tree, vidpf.py:243-245).

    `to_bytes()` serializes the collector state and both aggregators'
    incremental carries; `from_bytes()` restores a run that continues
    bit-identically.  The report store itself is the caller's to
    persist (a real deployment keeps uploads in a database); the
    checkpoint covers everything derived from them.
    """

    def __init__(self, mastic: Mastic, ctx: bytes, thresholds: dict,
                 reports: Optional[list],
                 verify_key: Optional[bytes] = None,
                 incremental: bool = True,
                 chunk_size: Optional[int] = None,
                 store=None, mesh=None, batch=None):
        from .chunked import ChunkedIncrementalRunner, HostReportStore

        if verify_key is None:
            verify_key = gen_rand(mastic.VERIFY_KEY_SIZE)
        self.mastic = mastic
        self.ctx = ctx
        self.thresholds = thresholds
        self.reports = reports
        self.verify_key = verify_key
        self.bm = BatchedMastic(mastic)
        # `batch` lets a device-batched client pipeline (e.g.
        # tools/northstar.py's shard_device loop) hand over marshalled
        # arrays directly — at fleet scale there is no scalar report
        # list to marshal (the scalar `reports` stays optional and is
        # only needed by the XOF-rejection fallback).
        if chunk_size is not None or store is not None:
            # At-scale path: reports stream through the device chunk
            # by chunk; the device never holds the whole batch.
            if store is None:
                store = HostReportStore.from_batch(
                    batch if batch is not None
                    else self.bm.marshal_reports(reports), chunk_size)
            self.store = store
            self.batch = None
            self.num_reports = store.num_reports
            self.runner = ChunkedIncrementalRunner(
                self.bm, verify_key, ctx, store, reports, mesh=mesh)
        else:
            self.store = None
            self.batch = (batch if batch is not None
                          else self.bm.marshal_reports(reports))
            self.num_reports = int(self.batch.nonces.shape[0])
            self.runner = (
                _IncrementalRunner(self.bm, verify_key, ctx, self.batch,
                                   reports)
                if incremental else None)
        if mesh is not None:
            if self.runner is None:
                raise ValueError(
                    "mesh sharding requires the incremental runner "
                    "(incremental=True or a chunk_size/store)")
            from ..parallel.mesh import shard_incremental_runner
            shard_incremental_runner(self.runner, mesh)
        self.level = 0
        self.prefixes: list = [(False,), (True,)]
        self.prev_agg_params: list = []
        self.heavy_hitters: list = []
        self.metrics: list = []  # one RoundMetrics per completed level
        # Each completed level's unsharded aggregate, one entry per
        # candidate prefix in frontier order (not checkpointed).
        self.aggregates: list = []
        self.profile_dir: Optional[str] = None  # jax.profiler target
        self.obs_tenant = ""     # telemetry label (set by the service)
        self.done = False

    def step(self) -> bool:
        """Run one level's aggregation round.  Returns True while more
        rounds remain.

        Telemetry (ISSUE 7): each round runs inside a "round" trace
        span (attrs: tenant/round/level/frontier_width/reports; chunk
        spans nest under it) and feeds the chunk-phase histograms +
        compile-vs-execute attribution (obs/devtime.observe_round).
        Profiling: when `self.profile_dir` is set (a directory path)
        — or once per process when `MASTIC_JAX_PROFILE=dir` is armed
        — the round executes under jax.profiler.trace; open the
        result with TensorBoard / xprof.  Per-round wall-clock always
        lands in metrics.extra["round_wall_ms"].

        ISSUE 10: `step()` is the `step_begin` / `step_finish` pair
        run back to back.  The overlapped epoch executor calls the
        halves split across tenants — begin dispatches this level's
        round without blocking, finish issues the one blocking sync
        and advances the frontier."""
        handle = self.step_begin()
        if handle is None:
            return False
        return self.step_finish(handle)

    def step_begin(self) -> Optional[dict]:
        """Dispatch one level's round without blocking (resident
        runner) or run it outright (chunked / from-root, where the
        intra-round pipeline owns the sync discipline — the handle's
        ``atomic`` flag says which happened).  Returns None when no
        rounds remain.  Every handle MUST be passed to `step_finish`
        — the frontier only advances there."""
        if self.done:
            return None
        if not self.prefixes:
            self.done = True
            return None
        level = self.level
        agg_param = (level, tuple(self.prefixes), level == 0)
        assert self.mastic.is_valid(agg_param, self.prev_agg_params)
        profile_dir = self.profile_dir or devtime.take_profile_dir()
        prof = (jax.profiler.trace(profile_dir)
                if profile_dir else None)
        tracer = obs_trace.get_tracer()
        span = tracer.start_detached_span(
            "round", tenant=self.obs_tenant, round=level,
            level=level, frontier_width=len(self.prefixes),
            reports=self.num_reports, profiled=bool(profile_dir))
        handle = {"agg_param": agg_param, "span": span, "prof": prof,
                  "t0": time.perf_counter(), "atomic": True,
                  "rh": None, "result": None}
        if prof is not None:
            prof.__enter__()
        try:
            with tracer.use_parent(span):
                if isinstance(self.runner, _IncrementalRunner):
                    # The resident round splits at the sync seam: the
                    # handle holds in-flight futures, finish() blocks.
                    handle["rh"] = self.runner.round_stage(agg_param)
                    handle["atomic"] = False
                elif self.runner is not None:
                    handle["result"] = self.runner.round(
                        agg_param, metrics_out=self.metrics)
                else:
                    handle["result"] = run_round(
                        self.bm, self.verify_key, self.ctx,
                        agg_param, self.batch, self.reports,
                        metrics_out=self.metrics)
        except BaseException as exc:
            self._step_cleanup(handle, error=exc)
            raise
        return handle

    def step_finish(self, handle: dict) -> bool:
        """Collect the staged round (blocking sync for a split
        handle), stamp its metrics, and advance the frontier.
        Returns True while more rounds remain."""
        tracer = obs_trace.get_tracer()
        try:
            if not handle["atomic"]:
                with tracer.use_parent(handle["span"]):
                    handle["result"] = self.runner.round_collect(
                        handle["rh"], metrics_out=self.metrics)
        except BaseException as exc:
            self._step_cleanup(handle, error=exc)
            raise
        agg_result = handle["result"]
        self._step_cleanup(handle)
        if self.metrics:
            self.metrics[-1].extra["round_wall_ms"] = round(
                (time.perf_counter() - handle["t0"]) * 1e3, 2)
            self.metrics[-1].validate_extra()
            devtime.observe_round(self.metrics[-1],
                                  tenant=self.obs_tenant)
        (level, _prefixes, _wc) = handle["agg_param"]
        self.prev_agg_params.append(handle["agg_param"])
        self.aggregates.append(list(agg_result))

        survivors = [
            prefix for (prefix, count) in zip(self.prefixes, agg_result)
            if count >= get_threshold(self.thresholds, prefix)
        ]
        if level < self.mastic.vidpf.BITS - 1:
            self.prefixes = [p + (bit,) for p in survivors
                             for bit in (False, True)]
        else:
            self.heavy_hitters = survivors
        self.level += 1
        if self.level >= self.mastic.vidpf.BITS or not self.prefixes:
            self.done = True
        return not self.done

    def _step_cleanup(self, handle: dict, error=None) -> None:
        """Close the round's profiler bracket and trace span exactly
        once (both halves may hit an exception path)."""
        prof = handle.pop("prof", None)
        if prof is not None:
            prof.__exit__(None, None, None)
        span = handle.pop("span", None)
        if span is not None:
            if error is not None:
                span.set_default("error", type(error).__name__)
            obs_trace.get_tracer().end_span(span)

    def result(self) -> list:
        return self.heavy_hitters

    def frontier(self) -> list:
        """The truncated-but-correct output after the last COMPLETED
        level (the collector service's deadline-degradation contract,
        drivers/service.py): the prefixes that passed every completed
        round's threshold.  A finished run's frontier IS its result;
        a run cut off mid-tree reports the survivors of the last
        completed level (recovered as the unique parents of the
        expanded candidate set — step() expands survivors into their
        children before returning).  Nothing is claimed about levels
        that never ran."""
        if self.done:
            return list(self.heavy_hitters)
        if self.level == 0:
            return []   # no round completed: nothing verified yet
        seen: dict = {}
        for p in self.prefixes:
            seen.setdefault(p[:-1], None)
        return list(seen)

    def rounds_completed(self) -> int:
        """Levels completed over the run's lifetime (survives
        checkpoint-resume; `metrics` only covers this process)."""
        return self.level

    # -- checkpoint / resume ---------------------------------------

    def to_bytes(self) -> bytes:
        """Serialize the run between levels (collector state + both
        carries + the rejection-fallback mask)."""
        import io

        from ..backend.incremental import carry_to_arrays
        from .chunked import ChunkedIncrementalRunner

        chunked = isinstance(self.runner, ChunkedIncrementalRunner)
        num_layouts = (len(self.runner.layouts)
                       if self.runner is not None else 0)
        data = {
            "meta": np.array(
                [_CKPT_VERSION, self.level, int(self.done),
                 0 if self.runner is None else 1,
                 self.mastic.vidpf.BITS, self.num_reports,
                 self.store.chunk_size if chunked else 0,
                 num_layouts], np.int64),
            "binding": _ckpt_binding(self.verify_key, self.ctx,
                                     self.thresholds),
            "prefixes": _paths_to_array(self.prefixes),
            "heavy_hitters": _paths_to_array(self.heavy_hitters),
            "prev_levels": np.array(
                [p[0] for p in self.prev_agg_params], np.int64),
            "prev_wc": np.array(
                [p[2] for p in self.prev_agg_params], bool),
        }
        if self.prev_agg_params:
            data["last_prefixes"] = _paths_to_array(
                self.prev_agg_params[-1][1])
        for d in range(num_layouts):
            data[f"layout_{d}"] = _paths_to_array(
                self.runner.layouts[d])
        if chunked:
            data["width"] = np.int64(self.runner.width)
            data["fallback"] = self.runner.fallback
            data.update(self.runner.state_arrays())
        elif self.runner is not None:
            data["width"] = np.int64(self.runner.width)
            data["fallback"] = self.runner.fallback
            data.update(carry_to_arrays(self.runner.carries[0], "c0_"))
            data.update(carry_to_arrays(self.runner.carries[1], "c1_"))
        buf = io.BytesIO()
        np.savez(buf, **data)
        return buf.getvalue()

    @classmethod
    def from_bytes(cls, mastic: Mastic, ctx: bytes, thresholds: dict,
                   reports: Optional[list], verify_key: bytes,
                   data: bytes, store=None,
                   mesh=None, batch=None) -> "HeavyHittersRun":
        """Restore a checkpointed run over the same report store (a
        chunked run may pass `store` instead of scalar reports; a
        resident run built from a marshalled `batch` passes the same
        batch back — there is no scalar list at fleet scale)."""
        import io

        from ..backend.incremental import (carry_from_arrays,
                                           needed_paths)
        from .chunked import ChunkedIncrementalRunner

        arrays = np.load(io.BytesIO(data), allow_pickle=False)
        meta = [int(x) for x in arrays["meta"]]
        version = meta[0]
        num_layouts = 0
        if version == 1:
            (_, level, done, incremental, bits, num_reports) = meta
            chunk_size = 0
        elif version == 2:
            (_, level, done, incremental, bits, num_reports,
             chunk_size) = meta
        elif version == _CKPT_VERSION:
            (_, level, done, incremental, bits, num_reports,
             chunk_size, num_layouts) = meta
        else:
            raise ValueError(f"unknown checkpoint version {version}")
        if chunk_size == 0 and store is not None:
            # Passing a store would silently build the OTHER runner
            # kind and die on (or worse, skip) the missing per-chunk
            # carry arrays — refuse descriptively instead.
            raise ValueError(
                "checkpoint was taken by the resident (unchunked) "
                "runner; restore it with scalar reports, not a store")
        if chunk_size and store is None and reports is None:
            raise ValueError(
                "chunked checkpoint needs its report store (or the "
                "scalar reports to rebuild one)")
        if chunk_size == 0 and reports is None and batch is None:
            raise ValueError(
                "resident checkpoint needs the scalar reports (or the "
                "marshalled batch) it was taken over")
        restored_n = (store.num_reports if store is not None
                      else int(batch.nonces.shape[0])
                      if batch is not None else len(reports))
        if bits != mastic.vidpf.BITS or num_reports != restored_n:
            raise ValueError("checkpoint does not match this "
                             "instantiation / report store")
        if chunk_size and store is not None \
                and store.chunk_size != chunk_size:
            raise ValueError(
                f"checkpoint was taken with chunk_size={chunk_size}, "
                f"store has {store.chunk_size}")
        if not np.array_equal(np.asarray(arrays["binding"]),
                              _ckpt_binding(verify_key, ctx,
                                            thresholds)):
            raise ValueError("checkpoint was taken under a different "
                             "verify_key / ctx / thresholds")

        run = cls(mastic, ctx, thresholds, reports,
                  verify_key=verify_key, incremental=bool(incremental),
                  chunk_size=chunk_size if chunk_size else None,
                  store=store, mesh=mesh, batch=batch)
        run.level = level
        run.done = bool(done)
        run.prefixes = _paths_from_array(arrays["prefixes"])
        run.heavy_hitters = _paths_from_array(arrays["heavy_hitters"])
        prev_levels = [int(x) for x in arrays["prev_levels"]]
        prev_wc = [bool(x) for x in arrays["prev_wc"]]
        last_prefixes: tuple = ()
        if prev_levels:
            last_prefixes = tuple(
                _paths_from_array(arrays["last_prefixes"]))
        # is_valid consumes only the weight-check flags and the last
        # level; the last round's prefixes are kept exactly because
        # they also determine the runner's carried paths.
        run.prev_agg_params = [
            (lvl, last_prefixes if i == len(prev_levels) - 1 else (),
             wc)
            for (i, (lvl, wc)) in enumerate(zip(prev_levels, prev_wc))
        ]
        def restored_layouts():
            """v3 saves the creation layouts; v1/v2 carries were
            compacted every round, so their arrangement equals the
            needed-paths of the last aggregation parameter."""
            if version >= 3:
                return [
                    _paths_from_array(arrays[f"layout_{d}"])
                    for d in range(num_layouts)
                ]
            return needed_paths(last_prefixes, prev_levels[-1])

        if isinstance(run.runner, ChunkedIncrementalRunner) \
                and prev_levels:
            from ..backend.incremental import IncrementalMastic
            from .chunked import check_envelope

            runner = run.runner
            width = int(arrays["width"])
            if width != runner.width:
                # A checkpoint taken at a grown width must re-clear
                # the envelope on the restoring host/chip — adopting
                # it unchecked would OOM with a raw allocator error
                # instead of the guard's refusal.
                check_envelope(runner.bm, runner.store.chunk_size,
                               width, runner.num_reports,
                               runner.n_device_shards)
                runner.width = width
                runner.engine = IncrementalMastic(runner.bm, width)
                runner._eval_fn = None
                runner._combine_fn = None
            runner.fallback = np.asarray(arrays["fallback"], bool)
            runner.load_state(arrays, runner.store.num_chunks)
            runner.layouts = restored_layouts()
        elif run.runner is not None and prev_levels:
            from ..backend.incremental import IncrementalMastic

            runner = run.runner
            width = int(arrays["width"])
            if width != runner.width:
                # Re-point the engine at the stored width directly —
                # the freshly-initialized carries are about to be
                # replaced wholesale, so _grow's padding would be
                # wasted device work.
                runner.width = width
                runner.engine = IncrementalMastic(runner.bm, width)
                runner._eval_fn = None
                runner._combine_fn = None
            runner.fallback = np.asarray(arrays["fallback"], bool)
            runner.carries = [
                carry_from_arrays(arrays, "c0_"),
                carry_from_arrays(arrays, "c1_"),
            ]
            if runner.mesh is not None:
                from ..parallel.mesh import place_reports
                runner.carries = [place_reports(runner.mesh, c)
                                  for c in runner.carries]
            runner.layouts = restored_layouts()
        return run


class RoundPrograms:
    """Shared round-program machinery for the incremental runners.

    The resident (_IncrementalRunner) and chunked
    (drivers/chunked.ChunkedIncrementalRunner) runners execute the
    identical round program — one definition keeps their semantics
    locked together.  Subclasses provide bm / verify_key / ctx /
    engine / width / layouts / mesh and a _grow(width), and call
    _init_programs() from __init__.

    Two program tiers:

    * `_eval_jit` / `_combine_jit` — the jitted functions (the mesh
      path calls them directly: GSPMD needs jit's sharding
      propagation);
    * `self.programs` (drivers/pipeline.ProgramCache) — ahead-of-time
      compiled executables keyed by the shapes each round actually
      closes over (chunk rows, padded width, the pow2 binder/out
      buckets).  Shape-keying makes width growth safe by
      construction: a grown round's key differs, so no invalidation
      step can be forgotten (the r5..r8 code cleared `_eval_fn` /
      `_agg_fn` on _grow but left `_wc_fns` — benign only because
      the weight-check program's input shapes happen to be
      width-independent; tests/test_pipeline.py locks the
      grow-then-weight-check path either way).  `_warm_next`
      compiles the predicted next level's programs while the current
      round's dispatched device work is still executing (PERF.md:
      the measured ~100 s of inline compile in the production
      round); see ProgramCache for why this is synchronous rather
      than a compiler thread.
    """

    def _init_programs(self) -> None:
        from . import artifacts
        from .pipeline import ProgramCache

        self._eval_fn = None
        self._combine_fn = None
        self._wc_fns: dict = {}
        self._rk_fn_jit = None
        # Runtime + family suffix on every program key: a program
        # compiled under a different jax build/backend, or for a
        # different instantiation/ctx, can never be served — in
        # process (ProgramCache refuses skewed runtimes) or from the
        # AOT artifact store (drivers/artifacts.py, ROADMAP item 4).
        self._key_suffix = (artifacts.runtime_tag(),
                            artifacts.family_id(self.bm, self.ctx))
        self.programs = ProgramCache(store=artifacts.store_from_env())
        self._warmed_keys: set = set()
        self._stats_mark = dict(self.programs.stats)

    # -- mesh plumbing (report-axis data parallelism) --------------

    def _mesh_shards(self) -> int:
        """Report-axis size of the installed mesh (0 = no mesh) — part
        of every program-cache key, so a grown-or-resharded runner maps
        to fresh keys instead of replaying a mismatched executable."""
        return (self.mesh.shape["reports"] if self.mesh is not None
                else 0)

    def _rep_sharding(self):
        from jax.sharding import NamedSharding, PartitionSpec as P

        return NamedSharding(self.mesh, P("reports"))

    def _repl_sharding(self):
        from jax.sharding import NamedSharding, PartitionSpec as P

        return NamedSharding(self.mesh, P())

    # Whether the eval program donates its carry args.  True for the
    # runners (in-process compiles: donation halves the transient
    # carry footprint).  artifacts.make_baker sets False: an
    # executable with input-output aliasing DOUBLE-FREES its donated
    # buffers when deserialized on this jaxlib CPU (heap corruption,
    # allocator-state dependent, invisible to the output probe —
    # PERF.md §11), so baked programs must be donation-free and
    # ArtifactStore.save refuses donating executables outright.
    _donate_carries = True

    def _donation_safe(self) -> bool:
        """Donation is only safe when the executable can never come
        back DESERIALIZED.  The artifact store enforces that by
        refusing donating executables (PERF.md §11), but jax's own
        persistent compilation cache (JAX_COMPILATION_CACHE_DIR)
        deserializes jitted executables on a hit behind our back —
        same double-free, different loader.  Observed live in the WAL
        kill-9 drill: a restarted collector whose level-0 eval came
        from the warm shared cache corrupted the heap, and the FLP
        weight check then rejected every report (or segfaulted at
        teardown) while a cold-compiling child never failed.  So:
        drop donation whenever this process's persistent cache is on
        (JAX reads JAX_COMPILATION_CACHE_DIR into this config at
        import; entry points set it through mastic_tpu/compile_cache).
        The memory model prices the second carry copy that costs
        (chunked.carry_copies)."""
        from .chunked import carries_donated

        return self._donate_carries and carries_donated()

    def _eval_jit(self):
        if self._eval_fn is None:
            engine = self.engine
            ctx = self.ctx

            def both(vk, c0, c1, rnd, ext_rk, conv_rk, cws):
                (c0, proof0, out0, ok0) = engine.agg_round(
                    0, vk, ctx, c0, rnd, ext_rk, conv_rk, cws)
                (c1, proof1, out1, ok1) = engine.agg_round(
                    1, vk, ctx, c1, rnd, ext_rk, conv_rk, cws)
                accept = jnp.all(proof0 == proof1, axis=-1)
                return (c0, c1, out0, out1, accept, ok0 & ok1)

            # Carries are donated (unless _donate_carries is off, the
            # bake path): both runners replace them with the outputs
            # (resident keeps them resident; chunked re-uploads fresh
            # buffers every chunk).  The verify key is traced so a
            # fresh per-collection key reuses the compiled program.
            # Under a mesh every output is pinned report-sharded so the
            # eval -> combine handoff has deterministic shardings (the
            # AOT warm lowers against exactly these).
            kwargs: dict = {}
            if self._donation_safe():
                kwargs["donate_argnums"] = (1, 2)
            if self.mesh is not None:
                rep = self._rep_sharding()
                kwargs["out_shardings"] = (rep,) * 6
            self._eval_fn = jax.jit(both, **kwargs)
        return self._eval_fn

    def _combine_jit(self):
        """Accept-mask combine + masked aggregation, fully on device:
        the pipelined round's replacement for the host-side boolean
        folds that forced a blocking `np.asarray` wall between the
        tree step and the aggregate.  Rounds without a weight check
        pass all-ones for the three wc masks, so one program
        signature serves every level-kind; limb arithmetic is exact
        modular integer math, so the fused masked sum is bit-equal to
        the old standalone aggregate."""
        if self._combine_fn is None:
            bm = self.bm

            def combine(out0, out1, accept_eval, ok, valid,
                        wc_accept, wc_ok, jr):
                accept = (accept_eval & ok & valid
                          & wc_accept & wc_ok & jr)
                return (accept, bm.aggregate(out0, accept),
                        bm.aggregate(out1, accept))

            kwargs: dict = {}
            if self.mesh is not None:
                # The masked sum over the report-sharded axis is THE
                # round's only cross-chip collective: GSPMD lowers it
                # to per-shard partial sums + a psum over ICI, and the
                # replicated output sharding makes that explicit.
                # Field addition is exact modular integer math, so the
                # shard-then-psum order is bit-identical to the serial
                # single-device sum.
                kwargs["out_shardings"] = (
                    self._rep_sharding(), self._repl_sharding(),
                    self._repl_sharding())
            self._combine_fn = jax.jit(combine, **kwargs)
        return self._combine_fn

    # -- shape-keyed AOT programs (drivers/pipeline.py) ------------

    def _eval_key(self, rows: int, plan) -> tuple:
        from .pipeline import plan_shape_key

        return ("eval", rows, self._mesh_shards()) \
            + plan_shape_key(plan) + self._key_suffix

    def _agg_key(self, rows: int, out_cols: int) -> tuple:
        return ("agg", rows, self._mesh_shards(), out_cols) \
            + self._key_suffix

    def _wc_key(self, rows: int, level: int) -> tuple:
        return ("wc", rows, self._mesh_shards(), level) \
            + self._key_suffix

    def _rk_key(self, rows: int) -> tuple:
        # The AES key-schedule program runs before mesh placement on
        # every path, so the mesh shape is not part of its key.
        return ("rk", rows) + self._key_suffix

    def _preload_first_round(self, rows: int, rk_rows: int) -> int:
        """Pull the FIRST round's program set (level-0 eval/agg/wc +
        the key schedule) from the artifact store at construction —
        exactly the keys on the time-to-first-round critical path.
        Deeper levels prefetch in the predictor's overlapped warm
        slot instead (`ProgramCache.warm` consults the store before
        compiling), so their ~1.5 s-per-program load latency hides
        behind device execution rather than stacking up in front of
        round 0 (measured: whole-family preload put 10 sequential
        loads on the critical path and more than doubled the warm
        cold start)."""
        if self.programs.store is None:
            return 0
        from ..backend.incremental import RoundPlan

        plan0 = RoundPlan(((False,), (True,)), 0,
                          self.bm.m.vidpf.BITS, self.width, [])
        out_cols = len(plan0.out_idx) * (1 + self.bm.m.flp.OUTPUT_LEN)
        wanted = {self._eval_key(rows, plan0),
                  self._agg_key(rows, out_cols),
                  self._wc_key(rows, 0),
                  self._rk_key(rk_rows)}
        return self.programs.preload(lambda key: key in wanted)

    def _artifacts_block(self) -> dict:
        """The per-round `extra["artifacts"]` record (obs/schema.py):
        artifact hits vs inline compiles since the previous round —
        the stamp that makes "this round never traced" a measured
        claim in every metrics record."""
        s = self.programs.stats
        m = self._stats_mark
        block = {
            "store": (self.programs.store.path
                      if self.programs.store is not None else None),
            "hits": s["artifact_hits"] - m["artifact_hits"],
            "inline_compiles": (s["inline_compiles"]
                                - m["inline_compiles"]),
            "warm_compiles": (s["warm_compiles"]
                              - m["warm_compiles"]),
            "load_ms": round(s["artifact_load_ms"]
                             - m["artifact_load_ms"], 2),
        }
        self._stats_mark = dict(s)
        return block

    def _eval_program(self, rows: int, plan, args) -> tuple:
        """(program, compile_wait_seconds) for this round's eval:
        the cached AOT executable, compiled inline only when
        prediction missed.  Mesh rounds use the same path — lowering
        from the concretely placed args bakes their NamedShardings
        into the program (and the cache key carries the mesh shape),
        so steady-state sharded rounds are zero-inline-compile too."""
        return self.programs.get(
            self._eval_key(rows, plan),
            lambda: self._eval_jit().lower(*args))

    def _agg_program(self, rows: int, cargs) -> tuple:
        return self.programs.get(
            self._agg_key(rows, cargs[0].shape[1]),
            lambda: self._combine_jit().lower(*cargs))

    def _wc_program(self, rows: int, level: int, wcargs) -> tuple:
        """The weight-check (FLP) program through the same AOT cache
        tier as eval/agg: pre-r14 it was a bare per-level jit, so a
        cold process's FIRST round (level 0 runs the weight check)
        paid its full compile outside the artifact machinery."""
        return self.programs.get(
            self._wc_key(rows, level),
            lambda: self._wc_fn(level).lower(*wcargs))

    def _rk_jit(self):
        if self._rk_fn_jit is None:
            (bm, ctx) = (self.bm, self.ctx)
            self._rk_fn_jit = jax.jit(
                lambda n: bm.vidpf.roundkeys(ctx, n))
        return self._rk_fn_jit

    def _rk_program(self, rows: int, args) -> tuple:
        """The AES round-key schedule, AOT-cached: both runners pay
        it once at construction — the last compile standing between a
        warm artifact store and a trace-free cold start."""
        return self.programs.get(
            self._rk_key(rows),
            lambda: self._rk_jit().lower(*args))

    # -- abstract lowering signatures (bake + warm share these) ----

    def _sds(self, shape, dtype, sharding=None):
        if sharding is None:
            return jax.ShapeDtypeStruct(shape, dtype)
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    def _mesh_sh(self) -> tuple:
        return ((self._rep_sharding(), self._repl_sharding())
                if self.mesh is not None else (None, None))

    def _eval_structs(self, rows: int, plan) -> tuple:
        """The eval program's full abstract signature at `rows` —
        what `tools/bake.py` lowers against when no reports exist.
        Shapes mirror the runners' concrete args exactly (per-report
        tensors report-sharded under a mesh, small round inputs
        replicated); drift between this and a real call surfaces as
        a cache miss, never a wrong program."""
        from ..backend.incremental import Carry, round_inputs
        from ..backend.vidpf_jax import BatchedCorrectionWords

        (rep, repl) = self._mesh_sh()
        vid = self.bm.vidpf
        (bits, vl) = (vid.BITS, vid.VALUE_LEN)
        n = self.bm.spec.num_limbs
        w = plan.width
        carry = Carry(
            w=self._sds((rows, bits, w, vl, n), jnp.uint32, rep),
            proof=self._sds((rows, bits, w, 32), jnp.uint8, rep),
            seed=self._sds((rows, w, 16), jnp.uint8, rep),
            ctrl=self._sds((rows, w), jnp.bool_, rep))
        rnd = jax.tree_util.tree_map(
            lambda x: self._sds(x.shape, x.dtype, repl),
            round_inputs(plan))
        (erk, crk) = jax.eval_shape(
            lambda nn: self.bm.vidpf.roundkeys(self.ctx, nn),
            jax.ShapeDtypeStruct((rows, 16), jnp.uint8))
        cws = BatchedCorrectionWords(
            seed=self._sds((rows, bits, 16), jnp.uint8, rep),
            ctrl=self._sds((rows, bits, 2), jnp.bool_, rep),
            w=self._sds((rows, bits, vl, n), jnp.uint32, rep),
            proof=self._sds((rows, bits, 32), jnp.uint8, rep))
        vk = self._sds((self.bm.m.VERIFY_KEY_SIZE,), jnp.uint8, repl)
        return (vk, carry, carry, rnd,
                self._sds(erk.shape, erk.dtype, rep),
                self._sds(crk.shape, crk.dtype, rep), cws)

    def _agg_structs(self, rows: int, out_cols: int) -> tuple:
        (rep, _repl) = self._mesh_sh()
        n = self.bm.spec.num_limbs
        s_out = self._sds((rows, out_cols, n), jnp.uint32, rep)
        s_mask = self._sds((rows,), jnp.bool_, rep)
        return (s_out, s_out) + (s_mask,) * 6

    def _batch_structs(self, rows: int):
        from ..backend.mastic_jax import ReportBatch
        from ..backend.vidpf_jax import BatchedCorrectionWords

        (rep, _repl) = self._mesh_sh()
        m = self.bm.m
        vid = self.bm.vidpf
        (bits, vl) = (vid.BITS, vid.VALUE_LEN)
        n = self.bm.spec.num_limbs
        use_jr = m.flp.JOINT_RAND_LEN > 0

        def u8(*shape):
            return self._sds(shape, jnp.uint8, rep)

        return ReportBatch(
            nonces=u8(rows, 16),
            cws=BatchedCorrectionWords(
                seed=u8(rows, bits, 16),
                ctrl=self._sds((rows, bits, 2), jnp.bool_, rep),
                w=self._sds((rows, bits, vl, n), jnp.uint32, rep),
                proof=u8(rows, bits, 32)),
            keys=u8(rows, 2, 16),
            leader_proofs=self._sds((rows, m.flp.PROOF_LEN, n),
                                    jnp.uint32, rep),
            helper_seeds=u8(rows, 32),
            leader_seeds=u8(rows, 32) if use_jr else None,
            peer_parts=tuple(u8(rows, 32) if use_jr else None
                             for _ in range(2)))

    def _wc_structs(self, rows: int) -> tuple:
        (rep, repl) = self._mesh_sh()
        vid = self.bm.vidpf
        n = self.bm.spec.num_limbs
        vk = self._sds((self.bm.m.VERIFY_KEY_SIZE,), jnp.uint8, repl)
        w = self._sds((rows, 2, vid.VALUE_LEN, n), jnp.uint32, rep)
        return (vk, self._batch_structs(rows), w, w)

    def _rk_structs(self, rows: int) -> tuple:
        return (self._sds((rows, 16), jnp.uint8),)

    def _warm_next(self, plan, args, rows: int) -> float:
        """Ahead-of-time compile the predicted next level's (bucket,
        width) programs.  Called at the point where every in-flight
        chunk's device work is already dispatched and the host is
        about to idle in the round's blocking sync, so the XLA work
        overlaps device execution (async dispatch keeps the device
        computing through it).  Lowering signatures are built from
        this round's concrete args with the predicted plan's
        traced-input shapes swapped in — no device memory is touched.
        Returns the seconds spent (the timeline's warm_ms)."""
        from ..backend.incremental import round_inputs
        from . import pipeline as pl

        if not pl.pipeline_enabled():
            return 0.0
        structs = jax.tree_util.tree_map(pl.to_struct, args)
        layouts_next = list(self.layouts) + [plan.layout_new]
        out_len = 1 + self.bm.m.flp.OUTPUT_LEN
        n = self.bm.spec.num_limbs
        eval_jit = self._eval_jit()
        combine_jit = self._combine_jit()
        # Mesh rounds warm with the shardings the real call passes:
        # per-report tensors P("reports"), the small round inputs
        # replicated (mirroring place_reports / place_replicated in
        # the runners' stage phase).
        (rep, repl) = ((self._rep_sharding(), self._repl_sharding())
                       if self.mesh is not None else (None, None))

        def struct(shape, dtype, sharding):
            if sharding is None:
                return jax.ShapeDtypeStruct(shape, dtype)
            return jax.ShapeDtypeStruct(shape, dtype,
                                        sharding=sharding)

        spent = 0.0
        for nplan in pl.predicted_next_plans(
                plan.prefixes, plan.level, self.bm.m.vidpf.BITS,
                self.width, layouts_next):
            nrnd = jax.tree_util.tree_map(
                lambda x: struct(x.shape, x.dtype, repl),
                round_inputs(nplan))
            eargs = structs[:3] + (nrnd,) + structs[4:]
            ekey = self._eval_key(rows, nplan)
            self._warmed_keys.add(ekey)
            spent += self.programs.warm(
                ekey, lambda: eval_jit.lower(*eargs))
            out_cols = len(nplan.out_idx) * out_len
            s_out = struct((rows, out_cols, n), jnp.uint32, rep)
            s_mask = struct((rows,), jnp.bool_, rep)
            cargs = (s_out, s_out) + (s_mask,) * 6
            akey = self._agg_key(rows, out_cols)
            self._warmed_keys.add(akey)
            spent += self.programs.warm(
                akey, lambda: combine_jit.lower(*cargs))
        return spent

    def _aot_summary(self, rows: int, plan,
                     compile_wait_ms: float) -> dict:
        """The round's AOT record for RoundMetrics.extra: whether the
        eval key had been predicted+warmed, what the cache has done so
        far, and the compile wait this round actually paid."""
        key = self._eval_key(rows, plan)
        # Display form drops the runtime/family suffix (constant per
        # process; the full key is what the cache and store use).
        return {
            "eval_key": "x".join(str(k) for k in key[1:-2]),
            "predicted": key in self._warmed_keys,
            "compile_wait_ms": round(compile_wait_ms, 2),
            **self.programs.stats,
        }

    def _wc_fn(self, level: int):
        fn = self._wc_fns.get(level)
        if fn is None:
            (bm, ctx) = (self.bm, self.ctx)
            kwargs: dict = {}
            if self.mesh is not None:
                # Per-report verdict masks stay report-sharded so the
                # combine program's warm-lowered input shardings match.
                kwargs["out_shardings"] = self._rep_sharding()
            fn = jax.jit(lambda vk, b, w0, w1: bm.weight_check_device(
                vk, ctx, level, b, w0, w1), **kwargs)
            self._wc_fns[level] = fn
        return fn

    def _plan(self, prefixes, level):
        from ..backend.incremental import RoundPlan

        while True:
            try:
                return RoundPlan(prefixes, level,
                                 self.bm.m.vidpf.BITS, self.width,
                                 self.layouts)
            except ValueError as err:
                if "exceeds padded width" not in str(err):
                    raise
                self._grow(self.width * 2)


class _IncrementalRunner(RoundPrograms):
    """Drives backend/incremental.py across the collector loop: keeps
    both aggregators' carries, grows the padded width on demand
    (recompiling at most log2(max_width) times), and folds the
    weight-check FLP verdict of the level-0 round in via the fused
    round program."""

    def __init__(self, bm: BatchedMastic, verify_key: bytes, ctx: bytes,
                 batch: ReportBatch, reports: Optional[list] = None,
                 width: int = 8):
        from ..backend.incremental import IncrementalMastic

        self.bm = bm
        self.verify_key = verify_key
        self.ctx = ctx
        self.batch = batch
        self.reports = reports
        self.num_reports = int(batch.nonces.shape[0])
        # Reports whose XOF rejection sampling fired at some round:
        # their device carry holds garbage from that round onward, so
        # they are excluded from every subsequent device aggregate and
        # recomputed through the scalar layer each round instead.
        self.fallback = np.zeros(self.num_reports, bool)
        self.width = max(4, width)
        self.mesh = None  # set via parallel.mesh.shard_incremental_runner
        self.engine = IncrementalMastic(bm, self.width)
        self.layouts: list = []  # per-depth creation layouts
        self._init_programs()
        # Warm artifact store: the first round's programs land in
        # the in-process tier here, so even the key-schedule below
        # and round 0 never trace (drivers/artifacts.py); deeper
        # levels prefetch in the overlapped warm slot.
        self._preload_first_round(self.num_reports, self.num_reports)
        (rk_prog, _rk_wait) = self._rk_program(self.num_reports,
                                               (batch.nonces,))
        (self.ext_rk, self.conv_rk) = rk_prog(batch.nonces)
        self.carries = [
            self.engine.init_carry(self.num_reports,
                                   batch.keys[:, a], a)
            for a in range(2)
        ]

    def memory_accounting(self) -> dict:
        """Device-resident footprint: both carries, the round keys and
        the whole report batch live in HBM for the full run (the
        chunked runner's memory_accounting is the streaming twin —
        this mode only exists while the carry fits one chip)."""
        # .nbytes is metadata — no device->host transfer.
        carry = 2 * sum(x.nbytes for x in self.carries[0])
        rk = self.ext_rk.nbytes + self.conv_rk.nbytes
        batch = sum(x.nbytes
                    for x in jax.tree_util.tree_leaves(self.batch))
        return {
            "chunk_size": 0,
            "num_chunks": 1,
            "device_bytes_total": carry + rk + batch,
            "device_carry_bytes": carry,
            "host_bytes_total": 0,
        }

    def _grow(self, width: int) -> None:
        from ..backend.incremental import Carry, IncrementalMastic

        pad_nodes = width - self.width
        self.carries = [
            Carry(
                w=jnp.pad(c.w, ((0, 0), (0, 0), (0, pad_nodes),
                                (0, 0), (0, 0))),
                proof=jnp.pad(c.proof,
                              ((0, 0), (0, 0), (0, pad_nodes), (0, 0))),
                seed=jnp.pad(c.seed, ((0, 0), (0, pad_nodes), (0, 0))),
                ctrl=jnp.pad(c.ctrl, ((0, 0), (0, pad_nodes))),
            )
            for c in self.carries
        ]
        if self.mesh is not None:
            from ..parallel.mesh import place_reports
            self.carries = [place_reports(self.mesh, c)
                            for c in self.carries]
        self.width = width
        self.engine = IncrementalMastic(self.bm, width)
        # The AOT programs (self.programs) key on the shapes they
        # close over, so the grown width simply maps to fresh keys —
        # only the jitted closures (which capture the engine) need
        # rebinding.
        self._eval_fn = None
        self._combine_fn = None

    def round_stage(self, agg_param) -> dict:
        """The non-blocking half of one resident round: plan, program
        fetch, async dispatch of the whole eval -> weight-check ->
        mask-combine -> aggregate chain, the predicted-next-level
        warm slot, and the carry handover — everything short of the
        blocking sync.  Returns the in-flight handle
        `round_collect` consumes.  The overlapped epoch executor
        (drivers/service.py, ISSUE 10) calls the pair split across
        tenants: another tenant's stage runs here while this handle's
        device work computes."""
        from ..backend.incremental import round_inputs
        from .chunked import check_round_peak

        (level, prefixes, do_weight_check) = agg_param
        plan = self._plan(prefixes, level)
        acct = self.memory_accounting()
        check_round_peak(
            self.bm,
            len(plan.onehot_idx), len(plan.payload_parent),
            self.num_reports, acct["device_bytes_total"], level,
            (self.mesh.shape["reports"]
             if self.mesh is not None else 1),
            acct["device_carry_bytes"])
        from .pipeline import paused_gc

        t0 = time.perf_counter()
        with paused_gc():
            # GC paused for the dispatch window: its traces segfault
            # this jaxlib if a collection fires mid-trace
            # (pipeline.paused_gc).
            rnd = round_inputs(plan)
            vk_arr = _vk_array(self.verify_key)
            valid = jnp.asarray(~self.fallback)
            ones = jnp.ones(self.num_reports, bool)
            if self.mesh is not None:
                # Deterministic shardings for the AOT programs: small
                # round inputs replicated, per-report masks sharded
                # (mirrors the chunked runner's stage placement).
                from ..parallel.mesh import (place_replicated,
                                             place_reports)
                (rnd, vk_arr) = place_replicated(self.mesh,
                                                 (rnd, vk_arr))
                (valid, ones) = place_reports(self.mesh,
                                              (valid, ones))
            t_up = time.perf_counter()

            args = (vk_arr, self.carries[0], self.carries[1], rnd,
                    self.ext_rk, self.conv_rk, self.batch.cws)
            inline_before = self.programs.stats["inline_compiles"]
            (eval_prog, compile_s) = self._eval_program(
                self.num_reports, plan, args)
            t_disp0 = time.perf_counter()
            (c0, c1, out0, out1, accept_ev, ok) = eval_prog(*args)
            wc_checks = {}
            wc_compile_s = 0.0
            (wc_accept, wc_okdev, jr) = (ones, ones, ones)
            if do_weight_check:
                # FLP weight check on the depth-0 payload rows the
                # tree program just computed (rows 0..1 of depth 0 are
                # always the two root children) — a small FLP-only
                # program, not a second from-root tree eval.
                wcargs = (vk_arr, self.batch, c0.w[:, 0, :2],
                          c1.w[:, 0, :2])
                (wc_prog, wc_compile_s) = self._wc_program(
                    self.num_reports, level, wcargs)
                (wc_checks, wc_okdev) = wc_prog(*wcargs)
                wc_accept = wc_checks["weight_check"]
                jr = wc_checks.get("joint_rand", ones)
            cargs = (out0, out1, accept_ev, ok, valid,
                     wc_accept, wc_okdev, jr)
            (agg_prog, agg_compile_s) = self._agg_program(
                self.num_reports, cargs)
            (accept_dev, agg0, agg1) = agg_prog(*cargs)
            t_disp1 = time.perf_counter()
            # Everything is dispatched; the device computes while the
            # host compiles the predicted next level's programs.
            warm_s = self._warm_next(plan, args, self.num_reports)
        t_warm = time.perf_counter()
        self.carries = [c0, c1]
        assert level == len(self.layouts)
        self.layouts.append(plan.layout_new)
        return {
            "agg_param": agg_param, "plan": plan,
            "accept_dev": accept_dev, "agg0": agg0, "agg1": agg1,
            "ok": ok, "wc_okdev": wc_okdev, "accept_ev": accept_ev,
            "wc_checks": wc_checks,
            "compile_s": (compile_s, wc_compile_s, agg_compile_s),
            # Whether any of this round's program fetches actually
            # paid an inline XLA compile — an artifact-store load's
            # wait is attributed in extra["artifacts"].load_ms, and
            # the timeline compile field stays an inline-only claim.
            "compiled_inline": (self.programs.stats["inline_compiles"]
                                > inline_before),
            "warm_s": warm_s,
            "t": (t0, t_up, t_disp0, t_disp1, t_warm),
        }

    def round_collect(self, handle: dict,
                      metrics_out: Optional[list] = None) -> list:
        """The blocking half: the round's SINGLE sync, downloads, the
        scalar-fallback splice, metrics.  Everything in the handle is
        an in-flight future until here."""
        (level, prefixes, do_weight_check) = handle["agg_param"]
        agg_param = handle["agg_param"]
        plan = handle["plan"]
        (accept_dev, agg0, agg1) = (handle["accept_dev"],
                                    handle["agg0"], handle["agg1"])
        (ok, wc_okdev) = (handle["ok"], handle["wc_okdev"])
        (accept_ev, wc_checks) = (handle["accept_ev"],
                                  handle["wc_checks"])
        (compile_s, wc_compile_s, agg_compile_s) = handle["compile_s"]
        warm_s = handle["warm_s"]
        (t0, t_up, t_disp0, t_disp1, t_warm) = handle["t"]

        shard_skew = None
        if self.mesh is not None \
                and self.mesh.shape["reports"] > 1:
            # Per-shard completion skew inside the one sync window
            # (same probe as the chunked collect); observability only.
            t_sk = time.perf_counter()
            waits = []
            for sh in accept_dev.addressable_shards:
                sh.data.block_until_ready()
                waits.append((time.perf_counter() - t_sk) * 1e3)
            shard_skew = round(max(waits) - min(waits), 3)
        jax.block_until_ready(
            (accept_dev, agg0, agg1, ok, wc_okdev))
        t_wait = time.perf_counter()
        checks = {"eval_proof": np.asarray(accept_ev)}
        checks.update({k: np.asarray(v)
                       for (k, v) in wc_checks.items()})
        self.fallback |= ~np.asarray(ok)
        if do_weight_check:
            self.fallback |= ~np.asarray(wc_okdev)
        accept = np.asarray(accept_dev).copy()
        rows = len(prefixes) * (1 + self.bm.m.flp.OUTPUT_LEN)
        agg_shares = [
            self.bm.agg_share_to_host(np.asarray(a)[:rows])
            for a in (agg0, agg1)
        ]
        t_down = time.perf_counter()

        metrics = RoundMetrics(level=level,
                               frontier_width=len(prefixes),
                               padded_width=self.width,
                               reports_total=self.num_reports)
        attribute_rejections(metrics, checks["eval_proof"],
                             checks.get("weight_check"),
                             checks.get("joint_rand"),
                             device_ok=~self.fallback)
        # The incremental round extends only the surviving parents.
        count_round_ops(metrics, self.bm.m, self.num_reports,
                        2 * plan.parent_count,
                        include_key_setup=(level == 0))
        count_round_bytes(metrics, self.bm.m, agg_param,
                          self.num_reports)

        splice_rejected(self.bm.m, self.verify_key, self.ctx, agg_param,
                        self.reports, ~self.fallback, accept, agg_shares)
        metrics.accepted = int(accept.sum())
        metrics.xof_fallbacks = int(self.fallback.sum())
        metrics.rejected_fallback = int((self.fallback & ~accept).sum())
        t_host = time.perf_counter()
        # Inline-compile waits only: when every program came from the
        # cache/store tiers, the (small) fetch waits stay out of the
        # compile field — `artifacts.load_ms` attributes them.
        compile_ms = ((compile_s + agg_compile_s + wc_compile_s) * 1e3
                      if handle["compiled_inline"] else 0.0)
        metrics.extra["artifacts"] = self._artifacts_block()
        if self.mesh is not None:
            metrics.extra["mesh"] = {
                "report_shards": self.mesh.shape["reports"],
                "device_rows_per_chunk": self.num_reports,
                "rows_per_shard": (self.num_reports
                                   // self.mesh.shape["reports"]),
                "psum_bytes_per_round": agg0.nbytes + agg1.nbytes,
                "shard_wait_skew_ms_p50": shard_skew or 0.0,
                "shard_wait_skew_ms_max": shard_skew or 0.0,
            }
        metrics.extra["pipeline"] = {
            "mode": "resident-deferred",
            "fallback": None,
            "round_wall_ms": round((t_host - t0) * 1e3, 2),
            "overlap_efficiency": 0.0,  # one chunk: nothing to overlap
            "compile_inline_ms": round(compile_ms, 2),
            "phases": {
                "upload_ms": round((t_up - t0) * 1e3, 3),
                "compile_ms": round(compile_ms, 3),
                "dispatch_ms": round(
                    (t_disp1 - t_disp0 - agg_compile_s
                     - wc_compile_s) * 1e3, 3),
                "warm_ms": round(warm_s * 1e3, 3),
                "compute_wait_ms": round((t_wait - t_warm) * 1e3, 3),
                "download_ms": round((t_down - t_wait) * 1e3, 3),
                "host_ms": round((t_host - t_down) * 1e3, 3),
            },
            "host_syncs": 1,
            "aot": self._aot_summary(self.num_reports, plan,
                                     compile_ms),
        }
        if metrics_out is not None:
            metrics_out.append(metrics)
        num = int(accept.sum())
        return self.bm.m.unshard(agg_param, agg_shares, num)

    def round(self, agg_param,
              metrics_out: Optional[list] = None) -> list:
        """One resident round, pipelined-executor style: the whole
        eval -> weight-check -> mask-combine -> aggregate chain is
        dispatched asynchronously (device-side accept combine instead
        of host boolean folds), the predicted next level's programs
        warm in the background, and ONE blocking sync collects
        everything — the per-phase timeline lands in
        `RoundMetrics.extra["pipeline"]`.  `round_stage` /
        `round_collect` are the same round split at the sync seam
        (the overlapped epoch executor's unit of interleaving)."""
        return self.round_collect(self.round_stage(agg_param),
                                  metrics_out=metrics_out)

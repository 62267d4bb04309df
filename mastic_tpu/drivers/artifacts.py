"""Whole-round AOT artifact store: trace-free cold start (ROADMAP
item 4, the compiler-first refactor).

Steady-state rounds pay zero inline compile since r9, but every
*process* still pays the full trace+XLA bill before its first round
(100.8 s on the incremental round in the r5 chip session, PERF.md
§5) — exactly the cold start the r11 collector service eats on
restart or tenant admission.  This module lowers the round-program family ahead of time
to serialized artifacts a fresh process loads in seconds:

* **what is stored** — every `ProgramCache` entry kind ("eval" /
  "agg" / "wc" / "rk" over rows × width × pow2 buckets × mesh shape),
  as two forms per entry: the `jax.export` StableHLO module (the
  portable, inspectable, versioned artifact) and the native compiled
  executable (`jax.experimental.serialize_executable` — the form that
  actually skips XLA).  Measured on this fabric: deserializing the
  StableHLO still pays ~95% of the inline XLA compile, while the
  native executable loads in ~1.5 s against a ~21 s compile — so the
  native form is the load path and the StableHLO rides along for
  portability (a version-skewed store can be recompiled from it
  offline without the original Python);

* **how loads are gated** — three gates, in order: (a) the manifest's
  SHA-256 digest of the blob file (a corrupted store is detected
  before any byte is unpickled — reason ``corrupt``), (b) the
  key/runtime match (the artifact key embeds the jax version +
  backend it was compiled under; a skewed runtime refuses with reason
  ``version-skew`` instead of loading an ABI-incompatible
  executable), and (c) a **bit-identity probe round** on first use:
  deterministic inputs are regenerated from the artifact's input
  signature and the loaded executable's output digest must equal the
  digest the freshly-traced reference produced at bake time.  PERF.md
  §7 proved the XLA persistent-cache *reload* can be silently wrong
  on this fabric (a reloaded round program that rejected every
  report) — the probe is the non-negotiable soundness gate, not an
  optimization.  Any gate failure falls back to inline tracing with
  the attributed reason in `mastic_artifact_loads_total{outcome=...}`;

* **who loads** — `drivers/pipeline.ProgramCache` grows an artifact
  tier below the in-process tier (`store=`): a cache miss consults
  the store before compiling, and the predictor's `warm` prefetches
  from disk before falling back to XLA.  Runners preload their
  shape family at construction (`ProgramCache.preload`), the
  collector service preloads every tenant's family at startup and on
  tenant admission (`CollectorService`), and `tools/bake.py`
  enumerates the pow2 bucket × growth-path × mesh-shape family for a
  config and writes the store offline.

The blob payload is a pickle (the executable serialization jax ships
is pickle-based); the digest gate runs BEFORE any unpickling, so the
trust boundary is filesystem permissions on the store directory —
the same boundary as the service snapshot.  The store is a local
directory, `MASTIC_ARTIFACT_DIR` / `--artifact-dir` select it.
"""

import hashlib
import json
import os
import pickle
import time
from typing import Callable, Optional, Sequence

import numpy as np

from ..obs import trace as obs_trace
from ..obs.registry import get_registry
from . import wal as wal_mod

ARTIFACT_VERSION = 1
MANIFEST_NAME = "manifest.json"

# Load outcomes (the mastic_artifact_loads_total label values).
HIT = "hit"
MISS = "miss"
PROBE_FAIL = "probe_fail"
VERSION_SKEW = "version_skew"
CORRUPT = "corrupt"

_PROBE_SEED = 0x6D617374  # "mast"; shared by bake and load sides

_runtime_tag: Optional[str] = None


def runtime_tag() -> str:
    """The runtime a compiled executable is only valid under:
    ``jax-<version>-<backend>``.  Part of every program-cache and
    artifact key, so a program compiled under a different jax build
    or backend can never be served — in process or from disk."""
    global _runtime_tag
    if _runtime_tag is None:
        import jax

        _runtime_tag = f"jax-{jax.__version__}-{jax.default_backend()}"
    return _runtime_tag


def check_key_runtime(key: tuple) -> None:
    """Refuse a program-cache key stamped for a different runtime.
    An in-process cache trivially matches; the gate exists for
    restored / cross-process key material, where serving a stale
    executable would be the PERF.md §7 failure mode with no probe in
    front of it."""
    tag = runtime_tag()
    for el in key:
        if isinstance(el, str) and el.startswith("jax-") and el != tag:
            raise RuntimeError(
                f"program key {key!r} was compiled under {el}, this "
                f"process runs {tag} — refusing to serve it (rebake "
                f"the artifact store for this runtime)")


def family_id(bm, ctx: bytes) -> str:
    """Digest binding a program family to the VDAF instantiation and
    collection context that are BAKED into the traced programs (the
    verify key is traced data; everything here is compile-time
    constant): algorithm ID, tree depth, payload/proof geometry,
    field, and the ctx bytes the domain-separation tags close over."""
    m = bm.m
    desc = [int(m.ID), int(m.vidpf.BITS), int(m.vidpf.VALUE_LEN),
            int(bm.spec.num_limbs), m.field.__name__,
            int(m.flp.PROOF_LEN), int(m.flp.OUTPUT_LEN),
            int(m.flp.JOINT_RAND_LEN), ctx.hex()]
    return hashlib.sha256(json.dumps(desc).encode()).hexdigest()[:16]


def _canon_key(key: Sequence) -> list:
    out = []
    for el in key:
        if isinstance(el, (bool, np.bool_)):
            out.append(bool(el))
        elif isinstance(el, (int, np.integer)):
            out.append(int(el))
        elif isinstance(el, str):
            out.append(el)
        else:
            raise TypeError(f"artifact key element {el!r} is not "
                            f"int/str")
    return out


def key_name(key: Sequence) -> str:
    """Content-addressed entry name for a program key."""
    canon = json.dumps(_canon_key(key))
    return hashlib.sha256(canon.encode()).hexdigest()[:24]


# -- deterministic probe inputs ---------------------------------------

def _gen_like(aval, rng: np.random.Generator) -> np.ndarray:
    """A deterministic array for one input aval.  Values only need to
    be deterministic, not meaningful: the probe compares the loaded
    executable's outputs against the freshly-traced reference's on
    the SAME inputs, and every op in the round programs is
    deterministic integer/boolean math (gather clamping included)."""
    dt = np.dtype(aval.dtype)
    if dt == np.bool_:
        return rng.integers(0, 2, aval.shape).astype(bool)
    if dt.kind in ("u", "i"):
        # Small positives: valid for index arrays (gathers stay in
        # range for any realistic dim) and exercise real carries in
        # the limb arithmetic.
        return rng.integers(0, 8, aval.shape).astype(dt)
    return rng.random(aval.shape).astype(dt)


def probe_inputs(executable, seed: int = _PROBE_SEED):
    """Regenerate the deterministic probe inputs for an executable
    from its own input signature, placed with its own input
    shardings (mesh executables need their inputs committed to the
    right devices before the call)."""
    import jax

    (arg_avals, kw_avals) = executable.in_avals
    rng = np.random.default_rng(seed)
    flat_avals = jax.tree_util.tree_leaves((arg_avals, kw_avals))
    flat = [_gen_like(a, rng) for a in flat_avals]
    (shardings, kw_sh) = executable.input_shardings
    # Shardings are pytree leaves, so a plain flatten pairs one
    # sharding per flattened input array.
    flat_sh = jax.tree_util.tree_leaves((shardings, kw_sh))
    if len(flat_sh) == len(flat):
        # placement comes from the loaded executable's own input
        # shardings, so mesh programs probe with mesh-correct inputs
        flat = [jax.device_put(x, s)  # mastic-allow: RB003 — the
                # sharding IS the executable's recorded input
                # placement, not a report upload path
                for (x, s) in zip(flat, flat_sh)]
    treedef = jax.tree_util.tree_structure((arg_avals, kw_avals))
    return jax.tree_util.tree_unflatten(treedef, flat)


def probe_digest(executable, seed: int = _PROBE_SEED) -> str:
    """SHA-256 over the executable's outputs on the deterministic
    probe inputs — computed identically at bake time (on the freshly
    traced program) and at load time (on the deserialized one); the
    two must be bit-equal or the reload is unsound."""
    import jax

    (args, kwargs) = probe_inputs(executable, seed)
    out = executable(*args, **kwargs)
    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(out):
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


# -- jax.export (the portable StableHLO form) -------------------------

_export_registered = False


def _register_export_types() -> None:
    """jax.export needs every custom pytree namedtuple registered
    once per process before an Exported can be serialized."""
    global _export_registered
    if _export_registered:
        return
    import jax.export as jax_export

    from ..backend.incremental import Carry, IncrementalRound
    from ..backend.mastic_jax import BatchedPrep, ReportBatch
    from ..backend.vidpf_jax import BatchedCorrectionWords, EvalState

    for t in (Carry, IncrementalRound, BatchedCorrectionWords,
              EvalState, ReportBatch, BatchedPrep):
        try:
            jax_export.register_namedtuple_serialization(
                t, serialized_name=f"mastic_tpu.{t.__name__}")
        except ValueError:  # mastic-allow: RB002 — already registered
            # by an earlier store in this process; idempotent by design
            pass
    _export_registered = True


def export_stablehlo(jit_fn, structs) -> Optional[bytes]:
    """The `jax.export` serialized StableHLO module for a jitted
    function at an abstract signature — the portable artifact form.
    Returns None when export is impossible (e.g. donation the
    exporter refuses): the native executable is the load path either
    way, so a missing StableHLO degrades portability, not function."""
    import zlib

    import jax.export as jax_export

    _register_export_types()
    try:
        exported = jax_export.export(jit_fn)(*structs)
        return zlib.compress(exported.serialize())
    except Exception:
        return None


# -- the store --------------------------------------------------------

class ArtifactStore:
    """A directory of digest-sealed compiled round programs.

    Layout: ``manifest.json`` plus one blob file per entry under
    ``blobs/`` (native executable pickle) and optionally ``hlo/``
    (compressed `jax.export` StableHLO).  Loaded-and-probed
    executables are memoized in memory, so per-epoch runner
    construction after a service preload is free.  Single-threaded by
    design, like the scheduler that owns it (drivers/service.py):
    bake tools, runners and the collector service all touch the
    store from the one scheduler/driver thread — the status-server
    thread never does."""

    def __init__(self, path: str):
        self.path = os.path.abspath(path)
        self._loaded: dict = {}     # name -> probed compiled
        self._failed: dict = {}     # name -> outcome (negative memo)
        self.manifest = self._read_manifest()

    def _read_manifest(self) -> dict:
        try:
            with open(os.path.join(self.path, MANIFEST_NAME)) as fh:
                man = json.load(fh)
        except (OSError, ValueError):
            return {"version": ARTIFACT_VERSION,
                    "runtime": runtime_tag(), "entries": {}}
        if not isinstance(man.get("entries"), dict):
            man["entries"] = {}
        return man

    def _write_manifest(self) -> None:
        # Crash-safe manifest (ISSUE 18 / RB006): tmp → fsync(file) →
        # atomic rename → fsync(dir), so a power cut never leaves a
        # half-written manifest NOR a rename whose bytes are still in
        # the page cache.
        os.makedirs(self.path, exist_ok=True)
        tmp = os.path.join(self.path, MANIFEST_NAME + ".tmp")
        with open(tmp, "w") as fh:
            json.dump(self.manifest, fh, indent=1, sort_keys=True)
            fh.write("\n")
            fh.flush()
            os.fsync(fh.fileno())
        os.replace(tmp, os.path.join(self.path, MANIFEST_NAME))
        wal_mod.fsync_dir(self.path)

    def keys(self) -> list:
        """Every manifest entry's program key, as tuples."""
        return [tuple(e["key"])
                for e in self.manifest["entries"].values()]

    def has(self, key) -> bool:
        return key_name(key) in self.manifest["entries"]

    def entry_count(self) -> int:
        return len(self.manifest["entries"])

    def store_bytes(self) -> int:
        return sum(int(e.get("bytes", 0))
                   for e in self.manifest["entries"].values())

    # -- save (bake side) ------------------------------------------

    def save(self, key, compiled,
             stablehlo: Optional[bytes] = None) -> dict:
        """Seal one freshly-compiled executable into the store: the
        native serialized form behind a SHA-256 digest, the probe
        output digest of THIS (traced, never pickled) executable as
        the load-time bit-identity reference, and optionally the
        `jax.export` StableHLO module."""
        from jax.experimental import serialize_executable as se

        donated = tuple(getattr(compiled, "donate_argnums", ()) or ())
        if donated:
            raise ValueError(
                f"refusing to seal an executable with donated "
                f"arguments {donated}: input-output aliasing "
                f"DOUBLE-FREES its buffers when the executable is "
                f"deserialized on this fabric (heap corruption, "
                f"allocator-state dependent, invisible to the output "
                f"probe — PERF.md §11).  Bake via "
                f"artifacts.make_baker, which lowers donation-free")
        payload = pickle.dumps(se.serialize(compiled))
        digest = hashlib.sha256(payload).hexdigest()
        probe = probe_digest(compiled)
        name = key_name(key)
        try:
            devices = len(compiled.input_shardings[0][0].device_set)
        except Exception:
            devices = 1
        entry = {
            "key": _canon_key(key),
            "blob": f"blobs/{name}.pkl",
            "sha256": digest,
            "probe_digest": probe,
            "probe_seed": _PROBE_SEED,
            "devices": devices,
            "bytes": len(payload),
            "stablehlo": (f"hlo/{name}.stablehlo.zz"
                          if stablehlo else None),
        }
        os.makedirs(os.path.join(self.path, "blobs"), exist_ok=True)
        # The blob must be durable BEFORE the manifest names it
        # (ISSUE 18): a manifest entry pointing at unsynced bytes
        # would fail its sha256 gate on the next load after a crash.
        blob_path = os.path.join(self.path, entry["blob"])
        with open(blob_path, "wb") as f:
            f.write(payload)
            f.flush()
            os.fsync(f.fileno())
        wal_mod.fsync_dir(os.path.dirname(blob_path))
        if stablehlo:
            os.makedirs(os.path.join(self.path, "hlo"), exist_ok=True)
            with open(os.path.join(self.path, entry["stablehlo"]),
                      "wb") as f:
                f.write(stablehlo)
                f.flush()
                os.fsync(f.fileno())
        self.manifest["version"] = ARTIFACT_VERSION
        self.manifest["runtime"] = runtime_tag()
        self.manifest["entries"][name] = entry
        self._write_manifest()
        # The saved executable IS the freshly-traced one: memoize it
        # so a run in the baking process serves the traced object,
        # never a reload of it.
        self._loaded[name] = compiled
        return entry

    # -- load (serve side) -----------------------------------------

    def _gated_load(self, name: str, entry: dict):
        """(compiled | None, outcome) through the three gates; no
        memoization, no counting — `load` owns those."""
        import jax
        from jax.experimental import serialize_executable as se

        if self.manifest.get("version") != ARTIFACT_VERSION \
                or self.manifest.get("runtime") != runtime_tag():
            return (None, VERSION_SKEW)
        if int(entry.get("devices", 1)) > len(jax.devices()):
            return (None, VERSION_SKEW)
        try:
            with open(os.path.join(self.path, entry["blob"]),
                      "rb") as f:
                payload = f.read()
        except OSError:
            return (None, CORRUPT)
        # Gate (a): digest BEFORE any unpickling.
        if hashlib.sha256(payload).hexdigest() != entry["sha256"]:
            return (None, CORRUPT)
        try:
            # The executable's own devices (the first `devices` of the
            # host, as the runners' meshes take them): without them a
            # single-device program loads expecting one shard per
            # local device and fails its probe on any multi-device
            # host.
            loaded = se.deserialize_and_load(
                *pickle.loads(payload),
                execution_devices=jax.devices()[:int(
                    entry.get("devices", 1))])
        except Exception:
            return (None, CORRUPT)
        # Gate (c): the bit-identity probe round — the loaded
        # executable must reproduce the traced reference's outputs on
        # the deterministic probe inputs (PERF.md §7: a reload can be
        # silently wrong; this is the soundness gate).
        try:
            dig = probe_digest(loaded,
                               int(entry.get("probe_seed",
                                             _PROBE_SEED)))
        except Exception:
            return (None, PROBE_FAIL)
        if dig != entry["probe_digest"]:
            return (None, PROBE_FAIL)
        return (loaded, HIT)

    def load(self, key):
        """The gated load: returns the probed executable or None (the
        caller compiles inline).  Every call lands one observation in
        `mastic_artifact_loads_total{outcome=...}` and one
        ``artifact.load`` span with the store path + key attrs."""
        name = key_name(key)
        tracer = obs_trace.get_tracer()
        # the key's family component is a SHA-256 digest of the
        # public instantiation record + protocol ctx (wire-public);
        # no key or seed material reaches the span
        with tracer.span(  # mastic-allow: SF003 — key carries only
                # a digest of public instantiation+ctx, no secrets
                "artifact.load", store=self.path,
                key="/".join(str(k) for k in key)) as span:
            if name in self._loaded:
                outcome = HIT
                prog = self._loaded[name]
            elif name in self._failed:
                outcome = self._failed[name]
                prog = None
            else:
                entry = self.manifest["entries"].get(name)
                if entry is None:
                    (prog, outcome) = (None, MISS)
                else:
                    (prog, outcome) = self._gated_load(name, entry)
                    if prog is not None:
                        self._loaded[name] = prog
                    elif outcome != MISS:
                        self._failed[name] = outcome
            span.set(outcome=outcome)
        get_registry().counter("mastic_artifact_loads_total",
                               outcome=outcome).inc()
        return prog

    def preload(self, match: Optional[Callable] = None) -> dict:
        """Load (and probe) every manifest entry whose key passes
        `match` — service startup / tenant admission / runner
        construction call this so round paths never pay the disk
        latency inline.  Returns outcome counts."""
        counts: dict = {}
        for key in self.keys():
            if match is not None and not match(key):
                continue
            outcome = (HIT if self.load(key) is not None
                       else self._failed.get(key_name(key), MISS))
            counts[outcome] = counts.get(outcome, 0) + 1
        return counts


# -- process-wide store registry --------------------------------------

_stores: dict = {}


def default_store(path: str) -> ArtifactStore:
    """One shared ArtifactStore per path: the in-memory loaded-
    executable memo must be process-wide, or every epoch's fresh
    runner would re-pay the disk load + probe.  Same single-thread
    ownership contract as the store itself."""
    path = os.path.abspath(path)
    store = _stores.get(path)
    if store is None:
        store = ArtifactStore(path)
        _stores[path] = store
    return store


def store_from_env() -> Optional[ArtifactStore]:
    """The `MASTIC_ARTIFACT_DIR` lever, read per call (a long-lived
    process can be pointed at a store without restarting)."""
    path = os.environ.get("MASTIC_ARTIFACT_DIR", "").strip()
    return default_store(path) if path else None


# -- family enumeration (bake side) -----------------------------------

def planted_paths(bits: int, k: int) -> list:
    """Deterministic planted hitter paths: path i carries i's binary
    digits little-endian, so k paths diverge at the root and the
    per-depth ancestor counts (which set every pow2 bucket) are a
    pure function of (bits, k).  `bench.py --cold-start` and
    `tools/bake.py` share this, so a bake reproduces the measured
    run's frontier trajectory exactly."""
    return [tuple(bool((i >> d) & 1) for d in range(bits))
            for i in range(k)]


def trajectory(bits: int, paths: list):
    """Yield (level, prefixes) of a planted-path heavy-hitters run at
    threshold 1: survivors at each level are exactly the ancestors of
    the planted paths (every report's alpha is a planted path, so any
    ancestor has a full count and any other child has zero) — the
    same rule `HeavyHittersRun.step` applies."""
    prefixes = [(False,), (True,)]
    for level in range(bits):
        yield (level, tuple(prefixes))
        survivors = [p for p in prefixes
                     if any(tuple(path[:level + 1]) == p
                            for path in paths)]
        if level < bits - 1:
            prefixes = [p + (b,) for p in survivors
                        for b in (False, True)]


def growth_trajectory(bits: int, max_frontier: int):
    """Yield (level, prefixes) of the threshold-prunes-nothing phase:
    every candidate survives, the frontier doubles per level until
    `max_frontier` — the early levels of any run, and the width-growth
    path (`_grow`) the predictor deliberately leaves to inline
    compilation unless baked here."""
    prefixes = [(False,), (True,)]
    for level in range(bits):
        if len(prefixes) > max_frontier:
            return
        yield (level, tuple(prefixes))
        if level < bits - 1:
            prefixes = [p + (b,) for p in prefixes
                        for b in (False, True)]


def make_baker(bm, ctx: bytes, width: int = 8, mesh=None):
    """A lowering-only RoundPrograms host: the same jitted closures
    and cache keys the runners use (one definition — a baked program
    IS the runner's program), with no reports attached."""
    from ..backend.incremental import IncrementalMastic
    from .heavy_hitters import RoundPrograms

    class _Baker(RoundPrograms):
        # Baked executables must NOT donate: input-output aliasing
        # double-frees on deserialization (heap corruption on this
        # jaxlib CPU — found by the artifacts-smoke gate, PERF.md
        # §11).  ArtifactStore.save enforces this structurally.
        _donate_carries = False

        def __init__(self):
            self.bm = bm
            self.verify_key = bytes(bm.m.VERIFY_KEY_SIZE)
            self.ctx = ctx
            self.mesh = mesh
            self.width = max(4, width)
            self.engine = IncrementalMastic(bm, self.width)
            self.layouts: list = []
            self._init_programs()

        def _grow(self, new_width: int) -> None:
            self.width = new_width
            self.engine = IncrementalMastic(self.bm, new_width)
            self._eval_fn = None
            self._combine_fn = None

    return _Baker()


def bake_attribute_round(baker, store: ArtifactStore, rows: int,
                         attributes: Sequence[str],
                         with_stablehlo: bool = True) -> dict:
    """Seal the attribute-metrics round program (ISSUE 10 satellite:
    the from-root round rides the same artifact tier as
    eval/agg/wc/rk).  The program bakes per (attribute set, rows,
    mesh shape): the hashed prefixes are compile-time constants of
    the traced round, so the key carries their digest
    (`heavy_hitters.root_program_key`) and the serving config must
    bake the exact attribute list it collects — a mismatch is a cache
    miss that compiles inline, attributed, never a wrong program."""
    import jax.numpy as jnp

    from .attribute_metrics import _round_fn_masked, hash_attribute
    from .heavy_hitters import _round_fn, root_program_key
    from .pipeline import paused_gc

    (bm, ctx, mesh) = (baker.bm, baker.ctx, baker.mesh)
    m = bm.m
    prefixes = tuple(hash_attribute(m, a) for a in attributes)
    if len(set(prefixes)) != len(prefixes):
        raise ValueError("attribute hash collision; increase BITS")
    agg_param = (m.vidpf.BITS - 1, prefixes, True)
    (rep, repl) = baker._mesh_sh()
    vk = baker._sds((m.VERIFY_KEY_SIZE,), jnp.uint8, repl)
    batch = baker._batch_structs(rows)
    if mesh is not None:
        shards = mesh.shape["reports"]
        fn = _round_fn_masked(bm, ctx, agg_param, mesh)
        structs = (vk, batch, baker._sds((rows,), jnp.bool_, rep))
    else:
        shards = 0
        fn = _round_fn(bm, ctx, agg_param)
        structs = (vk, batch)
    key = root_program_key(bm, ctx, agg_param, rows, shards)
    stats = {"compiled": 0, "skipped": 0, "seconds": 0.0}
    if store.has(key):
        stats["skipped"] = 1
        return stats
    t0 = time.perf_counter()
    with paused_gc():
        compiled = fn.lower(*structs).compile()
    hlo = (export_stablehlo(fn, structs) if with_stablehlo else None)
    store.save(key, compiled, stablehlo=hlo)
    stats["compiled"] = 1
    stats["seconds"] = time.perf_counter() - t0
    return stats


def bake_trajectory(baker, store: ArtifactStore, rows: int,
                    levels, with_stablehlo: bool = True) -> dict:
    """Walk one frontier trajectory, compiling and sealing every
    program key the runners would need: the eval + agg pair per
    level's shape bucket, the weight-check program at level 0, and
    the AES round-key schedule once.  Keys already in the store (or
    compiled earlier this walk) are skipped, so overlapping
    trajectories cost nothing extra."""
    from .pipeline import paused_gc

    stats = {"compiled": 0, "skipped": 0, "seconds": 0.0}

    def seal(key, jit_fn, structs) -> None:
        if store.has(key):
            stats["skipped"] += 1
            return
        t0 = time.perf_counter()
        with paused_gc():
            compiled = jit_fn.lower(*structs).compile()
        hlo = (export_stablehlo(jit_fn, structs) if with_stablehlo
               else None)
        store.save(key, compiled, stablehlo=hlo)
        stats["compiled"] += 1
        stats["seconds"] += time.perf_counter() - t0

    rk_key = baker._rk_key(rows)
    seal(rk_key, baker._rk_jit(), baker._rk_structs(rows))
    out_len = 1 + baker.bm.m.flp.OUTPUT_LEN
    bits = baker.bm.m.vidpf.BITS
    for (level, prefixes) in levels:
        plan = baker._plan(prefixes, level)
        assert level == len(baker.layouts)
        baker.layouts.append(plan.layout_new)
        seal(baker._eval_key(rows, plan), baker._eval_jit(),
             baker._eval_structs(rows, plan))
        out_cols = len(plan.out_idx) * out_len
        seal(baker._agg_key(rows, out_cols), baker._combine_jit(),
             baker._agg_structs(rows, out_cols))
        if level == 0:
            seal(baker._wc_key(rows, 0), baker._wc_fn(0),
                 baker._wc_structs(rows))
        # The runtime predictor warms BOTH its candidate shapes per
        # round (steady one-child-per-parent + all-survive growth);
        # a candidate absent from the store falls back to an XLA
        # compile in the warm slot — measured at ~16 s per round on
        # the CPU fabric, dominating the warm cold start.  Bake the
        # candidate family too, so every runtime warm is a load.
        from .pipeline import predicted_next_plans

        for nplan in predicted_next_plans(plan.prefixes, level, bits,
                                          baker.width,
                                          list(baker.layouts)):
            seal(baker._eval_key(rows, nplan), baker._eval_jit(),
                 baker._eval_structs(rows, nplan))
            ncols = len(nplan.out_idx) * out_len
            seal(baker._agg_key(rows, ncols), baker._combine_jit(),
                 baker._agg_structs(rows, ncols))
        del plan  # plans hold per-level index arrays; keep bake lean
    return stats

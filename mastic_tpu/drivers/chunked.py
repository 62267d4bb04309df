"""Report-chunked incremental heavy hitters: the at-scale execution
model (PERF.md §4's production plan).

The incremental engine's cross-round carry is O(BITS x width) per
report — far beyond HBM at the north-star shape (1M reports x 256
bits).  The protocol is embarrassingly parallel across reports
(reference loop /root/reference/poc/examples.py:49-71 is per-report;
aggregation is a plain sum, mastic.py:384-397), so the production
model streams fixed-size report chunks through each round:

* the full report batch and every chunk's cross-round carry live in
  HOST memory; the device holds exactly one chunk's state at a time
  (the steady-state tile bench.py measures);
* all chunks share one compiled round program (the last chunk is
  padded with dead lanes, masked out of acceptance and aggregation);
* each chunk's aggregate share is accumulated on the host, so the
  collector-facing results are bit-identical to the unchunked runner
  (tests/test_chunked.py locks this).

Memory accounting (`memory_accounting()`) reports the per-chunk device
footprint vs the total host footprint — the numbers that justify the
design at shapes where the unchunked carry cannot exist on one chip.
"""

import os
import time
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..common import vec_add
from ..metrics import (RoundMetrics, attribute_rejections,
                       count_round_bytes, count_round_ops)
from ..backend.mastic_jax import BatchedMastic, ReportBatch

# Memory budgets the feasibility guard enforces (PERF.md §4 derives
# the envelope at the north-star shape).  The device default is a
# conservative single-chip HBM allowance (16 GiB parts, XLA scratch
# headroom); <= 0 disables a budget.
DEVICE_BUDGET_DEFAULT = 12 << 30

# Double buffering: the pipelined executor (drivers/pipeline.py)
# keeps exactly one extra chunk's resident state in flight.
PIPELINE_CHUNKS_IN_FLIGHT = 2


def _device_budget() -> int:
    return int(os.environ.get("MASTIC_DEVICE_BUDGET_BYTES",
                              DEVICE_BUDGET_DEFAULT))


def carries_donated() -> bool:
    """Whether the round programs donate their carry inputs.  They do
    unless this process's persistent compile cache is on: JAX
    deserializes a cached executable on a hit, and a deserialized
    executable that donates its inputs double-frees them
    (RoundPrograms._donation_safe)."""
    return not jax.config.jax_compilation_cache_dir


def carry_copies() -> int:
    """Copies of the carries a round holds at once: with donation the
    eval program writes its output carries over its inputs; without,
    inputs and outputs are live side by side."""
    return 1 if carries_donated() else 2


def _host_budget() -> int:
    env = os.environ.get("MASTIC_HOST_BUDGET_BYTES")
    if env is not None:
        return int(env)
    try:
        total = (os.sysconf("SC_PAGE_SIZE")
                 * os.sysconf("SC_PHYS_PAGES"))
    except (ValueError, OSError):
        return 0
    # A cgroup limit below physical RAM is where the OOM kill actually
    # lands — honor it (v2 then v1; "max" / absent means unlimited).
    for path in ("/sys/fs/cgroup/memory.max",
                 "/sys/fs/cgroup/memory/memory.limit_in_bytes"):
        try:
            with open(path) as f:
                text = f.read().strip()
            if text.isdigit():
                total = min(total, int(text))
        # best-effort cgroup probe: an absent / unreadable limit file
        # simply means no cgroup cap applies
        except OSError:  # mastic-allow: RB002 — absence means no limit
            pass
    return int(total * 0.9)


def per_report_bytes(bm: BatchedMastic, width: int) -> dict:
    """Analytic per-report footprint of the chunked execution model
    (the arrays init_carry / roundkeys / HostReportStore actually
    allocate; tests/test_chunked.py pins these against the real
    allocations).  All three scale linearly in reports, so the
    envelope below is exact, not an estimate."""
    vid = bm.vidpf
    spec = bm.spec
    bits = vid.BITS
    limb_bytes = vid.VALUE_LEN * spec.num_limbs * 4
    # Carry (backend/incremental.py Carry, both aggregators): the
    # w/proof planes carry the whole BITS x width capacity; seed/ctrl
    # only the newest depth.
    carry = 2 * (bits * width * (limb_bytes + 32) + width * (16 + 1))
    # Fixed-key AES schedules (vidpf_jax.roundkeys): 2 x (11, 16).
    roundkeys = 2 * 11 * 16
    # Report store row (HostReportStore.from_batch).
    store = (16                              # nonce
             + bits * (16 + 2 + limb_bytes + 32)   # correction words
             + 2 * 16                        # VIDPF keys
             + bm.m.flp.PROOF_LEN * spec.num_limbs * 4
             + 32)                           # helper seed
    if bm.m.flp.JOINT_RAND_LEN > 0:
        store += 32 + 2 * 32                 # leader seed + peer parts
    # Worst-case binder staging: every carried depth at full width
    # (real runs prune far below; the per-round gate uses the actual
    # buckets).
    cap = 1
    while cap < bits * width:
        cap *= 2
    return {"carry": carry, "roundkeys": roundkeys, "store": store,
            "binder_peak": _binder_staging_bytes(bm, cap, cap)}


def _binder_staging_bytes(bm: BatchedMastic, onehot_cap: int,
                          payload_cap: int) -> int:
    """Per-report bytes of transient eval-proof binder staging — the
    one cost model shared by the planning envelope (worst-case
    buckets) and the per-round gate (actual buckets).  An r5
    20k × 256 device-resident run OOMed on exactly this term: two
    4.92 GiB buffers at bucket 2048 on top of 5.25 GB of carries.

    The onehot check stages a 32-byte proof row per slot of ITS pow2
    bucket and the payload check a limb row per slot of ITS bucket —
    the two buckets diverge whenever the payload row count (internal
    ancestors) trails the onehot row count (all current children), so
    each term is priced at its own bucket and summed (ADVICE r5: a
    shared max() cap overstated the peak and refused runs that fit).
    ×2 aggregators, ×2 for the gather + hash staging copies XLA
    materializes side by side."""
    limb_bytes = bm.vidpf.VALUE_LEN * bm.spec.num_limbs * 4
    return 4 * (onehot_cap * 32 + payload_cap * limb_bytes)


def memory_envelope(bm: BatchedMastic, chunk_size: int, width: int,
                    num_reports: int,
                    n_device_shards: int = 1) -> dict:
    """The (chunk_size, width) feasibility envelope: what one chunk
    costs the device and what the whole run costs the host, plus the
    largest chunk size that fits the device budget at this width.
    PERF.md §4 walks the arithmetic at the 1M x 256 north star.

    With `n_device_shards` > 1 the chunk's report axis is mesh-sharded
    and every device-resident term divides by the shard count: the
    `*_per_shard` fields price ONE chip's residency (the numbers the
    per-device budget actually bounds; tests/test_mesh_pipeline.py
    locks them against real per-device allocations).  Device rows pad
    up to the shard multiple first (uneven chunks shard by padding +
    masking, not by uneven placement — jax refuses the latter).

    The `device_bytes_*` fields are what stays allocated between
    rounds.  A round holds more: without carry donation
    (`carry_copies`) its output carries sit beside its inputs, and
    the `*_round_*`, `*_peak_*` and `max_*` fields price that copy."""
    per = per_report_bytes(bm, width)
    per_chunk = per["carry"] + per["roundkeys"] + per["store"]
    copies = carry_copies()
    outputs = (copies - 1) * per["carry"]
    per_round = per_chunk + outputs
    shards = max(1, n_device_shards)
    dev_rows = -(-chunk_size // shards) * shards
    rows_per_shard = dev_rows // shards
    # Worst-case round peak: what a round holds + binder staging with
    # every carried depth at full width.  Informational for planning
    # (real runs prune far below it) — the gating that protects a run
    # is per-round at the ACTUAL bucket, check_round_peak below.
    per_peak = per_round + per["binder_peak"]
    device_budget = _device_budget()
    host_budget = _host_budget()
    # Carries and round keys are allocated per padded chunk row (the
    # tail chunk is padded to chunk_size); only the store keeps exactly
    # num_reports rows.
    padded_rows = -(-num_reports // chunk_size) * chunk_size
    host_total = (padded_rows * (per["carry"] + per["roundkeys"])
                  + num_reports * per["store"])
    # Pipelined streaming keeps TWO chunks' resident state in flight
    # (chunk i+1 uploads while chunk i computes/downloads;
    # drivers/pipeline.py) — the output carries and the binder staging
    # are paid once, only the chunk in its compute phase holds them.
    # The executor degrades to serial when this footprint would exceed
    # the budget (round_peak_bytes below, at the ACTUAL buckets).
    per_pipelined = PIPELINE_CHUNKS_IN_FLIGHT * per_chunk + outputs

    def fit(per_row: int) -> int:
        return device_budget // per_row if device_budget > 0 else 0

    return {
        "bits": bm.vidpf.BITS, "width": width,
        "chunk_size": chunk_size, "num_reports": num_reports,
        "per_report_bytes": per,
        "carry_copies": copies,
        "device_bytes_per_chunk": chunk_size * per_chunk,
        "device_round_bytes_per_chunk": chunk_size * per_round,
        "device_peak_bytes_per_chunk": chunk_size * per_peak,
        "pipeline_chunks_in_flight": PIPELINE_CHUNKS_IN_FLIGHT,
        "device_bytes_per_chunk_pipelined":
            PIPELINE_CHUNKS_IN_FLIGHT * chunk_size * per_chunk,
        "device_peak_bytes_per_chunk_pipelined":
            chunk_size * (per_pipelined + per["binder_peak"]),
        "max_pipelined_chunk_size_at_width": fit(per_pipelined),
        # Per-shard residency: what ONE chip of the report-axis mesh
        # holds.  The padded device rows divide evenly by design, so
        # these are exact, not estimates.
        "report_shards": shards,
        "device_rows_per_chunk": dev_rows,
        "rows_per_shard": rows_per_shard,
        "device_bytes_per_chunk_per_shard": rows_per_shard * per_chunk,
        "device_round_bytes_per_chunk_per_shard":
            rows_per_shard * per_round,
        "device_peak_bytes_per_chunk_per_shard":
            rows_per_shard * per_peak,
        "device_bytes_per_chunk_pipelined_per_shard":
            PIPELINE_CHUNKS_IN_FLIGHT * rows_per_shard * per_chunk,
        "device_peak_bytes_per_chunk_pipelined_per_shard":
            rows_per_shard * (per_pipelined + per["binder_peak"]),
        "max_chunk_size_at_width_sharded": shards * fit(per_round),
        "max_pipelined_chunk_size_at_width_sharded":
            shards * fit(per_pipelined),
        "host_bytes_total": host_total,
        "device_budget_bytes": device_budget,
        "host_budget_bytes": host_budget,
        "max_chunk_size_at_width": fit(per_round),
        "min_hosts": (-(-host_total // host_budget)
                      if host_budget > 0 else 1),
    }


def check_envelope(bm: BatchedMastic, chunk_size: int, width: int,
                   num_reports: int,
                   n_device_shards: int = 1) -> dict:
    """Refuse shapes outside the envelope with an actionable message:
    the device check bounds one chunk's live state — per chip when the
    chunk's report axis is mesh-sharded over `n_device_shards`
    devices; the host check bounds the carry store and names the
    multi-host answer when one host cannot hold it."""
    env = memory_envelope(bm, chunk_size, width, num_reports,
                          n_device_shards)
    per_chip = env["device_round_bytes_per_chunk_per_shard"]
    max_chunk = env["max_chunk_size_at_width_sharded"]
    if env["device_budget_bytes"] > 0 \
            and per_chip > env["device_budget_bytes"]:
        chip = (f" across {n_device_shards} chips"
                if n_device_shards > 1 else "")
        if max_chunk == 0:
            raise ValueError(
                f"width {width} at {bm.vidpf.BITS} bits needs "
                f"{per_chip / 2**30:.1f} GiB per chip{chip} even for a "
                f"single-report chunk (budget "
                f"{env['device_budget_bytes'] / 2**30:.1f} GiB) — the "
                f"width itself is infeasible at this budget; raise "
                f"MASTIC_DEVICE_BUDGET_BYTES or shard the chunk over "
                f"more devices")
        raise ValueError(
            f"chunk of {chunk_size} reports needs "
            f"{per_chip / 2**30:.1f} GiB per chip{chip} "
            f"at width {width} (budget "
            f"{env['device_budget_bytes'] / 2**30:.1f} GiB); the largest "
            f"feasible chunk_size at this width is "
            f"{max_chunk} — shrink the chunk, or "
            f"raise MASTIC_DEVICE_BUDGET_BYTES if the chip has more HBM")
    if env["host_budget_bytes"] > 0 \
            and env["host_bytes_total"] > env["host_budget_bytes"]:
        raise ValueError(
            f"{num_reports} reports need "
            f"{env['host_bytes_total'] / 2**30:.1f} GiB of host memory "
            f"at width {width} (budget "
            f"{env['host_budget_bytes'] / 2**30:.1f} GiB); split the "
            f"report store across >= {env['min_hosts']} hosts, each "
            f"running its own chunked runner over its shard (carries, "
            f"round keys and store are all per-report; only the "
            f"per-round aggregate shares cross hosts), or raise "
            f"MASTIC_HOST_BUDGET_BYTES")
    return env


def round_peak_bytes(bm: BatchedMastic, onehot_cap: int,
                     payload_cap: int, chunk_rows: int,
                     resident_bytes: int, n_device_shards: int = 1,
                     chunks_in_flight: int = 1,
                     carry_bytes: int = 0) -> int:
    """Per-chip peak of one round at the ACTUAL binder buckets:
    `chunks_in_flight` copies of the resident chunk state (the
    pipelined executor keeps two) plus ONE chunk's binder staging
    (only the chunk in its compute phase holds the staging buffers),
    plus, when the round programs do not donate their carries, that
    chunk's output carries (`carry_bytes`: the carries' share of
    `resident_bytes`; carry_copies).
    The single cost model behind check_round_peak (serial, raising)
    and the pipeline executor's degrade-to-serial decision
    (non-raising, drivers/chunked.ChunkedIncrementalRunner)."""
    staging = _binder_staging_bytes(bm, onehot_cap,
                                    payload_cap) * chunk_rows
    outputs = (carry_copies() - 1) * carry_bytes
    return -(-(chunks_in_flight * resident_bytes + outputs + staging)
             // n_device_shards)


def check_round_peak(bm: BatchedMastic, onehot_cap: int,
                     payload_cap: int, chunk_rows: int,
                     resident_bytes: int, level: int,
                     n_device_shards: int = 1,
                     carry_bytes: int = 0) -> None:
    """Per-round device-memory gate at the ACTUAL binder buckets.

    The construction-time envelope bounds resident state; the binder
    staging buffers scale with the pow2 buckets of the LIVE carried
    rows, which grow with depth and cannot be known up front without
    assuming the worst case (which would refuse prunable runs the
    hardware handles fine).  So both runners call this before each
    round with the plan's real buckets — proof staging priced at the
    onehot bucket, payload staging at the (usually smaller) payload
    bucket: a run that would OOM the chip mid-depth instead stops at
    the offending level with the remedy, and everything up to that
    level is checkpointable.  (r5: a 20k × 256 device-resident run
    died exactly this way, two 4.92 GiB staging buffers at bucket
    2048 surfacing as a remote-compile OOM.)
    """
    budget = _device_budget()
    if budget <= 0:
        return
    per_row = _binder_staging_bytes(bm, onehot_cap, payload_cap)
    staging = per_row * chunk_rows
    peak = round_peak_bytes(bm, onehot_cap, payload_cap, chunk_rows,
                            resident_bytes, n_device_shards,
                            carry_bytes=carry_bytes)
    if peak > budget:
        # Largest TOTAL chunk size (across all its device shards)
        # whose peak fits: (resident_scaled + per_row*rows)/shards
        # <= budget, with resident scaling with rows too — bound it
        # conservatively by keeping resident's per-row share.
        held = resident_bytes + (carry_copies() - 1) * carry_bytes
        per_row_resident = held // max(1, chunk_rows)
        max_rows = max(0, (budget * n_device_shards)
                       // (per_row + per_row_resident))
        raise ValueError(
            f"level {level}: binder buckets {onehot_cap} (onehot) / "
            f"{payload_cap} (payload) need "
            f"{staging / 2**30:.1f} GiB of staging on top of "
            f"{held / 2**30:.1f} GiB resident and output carries "
            f"({peak / 2**30:.1f} GiB peak per chip vs budget "
            f"{budget / 2**30:.1f} GiB) — checkpoint and resume with "
            f"a total chunk of <= {max_rows} reports (across its "
            f"{n_device_shards} device shard(s)), shard over more "
            f"devices, or raise MASTIC_DEVICE_BUDGET_BYTES")


class HostReportStore:
    """A report batch resident in host memory, sliced into fixed-size
    device chunks (the upload database of a real aggregator; the
    checkpoint note at SURVEY.md §5 scopes report persistence to the
    caller — this class is that caller-side store)."""

    def __init__(self, arrays: dict, num_reports: int, chunk_size: int):
        self.arrays = arrays
        self.num_reports = num_reports
        self.chunk_size = chunk_size
        self.num_chunks = -(-num_reports // chunk_size)
        self.use_jr = arrays.get("leader_seeds") is not None

    @classmethod
    def from_batch(cls, batch: ReportBatch,
                   chunk_size: int) -> "HostReportStore":
        """Adopt a marshalled batch (device arrays land back on host)."""
        arrays = {
            "nonces": np.asarray(batch.nonces),
            "cws_seed": np.asarray(batch.cws.seed),
            "cws_ctrl": np.asarray(batch.cws.ctrl),
            "cws_w": np.asarray(batch.cws.w),
            "cws_proof": np.asarray(batch.cws.proof),
            "keys": np.asarray(batch.keys),
            "leader_proofs": np.asarray(batch.leader_proofs),
            "helper_seeds": np.asarray(batch.helper_seeds),
            "leader_seeds": (None if batch.leader_seeds is None
                             else np.asarray(batch.leader_seeds)),
            "peer_parts": tuple(
                None if p is None else np.asarray(p)
                for p in batch.peer_parts),
        }
        return cls(arrays, int(batch.nonces.shape[0]), chunk_size)

    def chunk_bounds(self, i: int) -> tuple[int, int]:
        lo = i * self.chunk_size
        return (lo, min(lo + self.chunk_size, self.num_reports))

    def host_slice(self, x: np.ndarray, i: int) -> np.ndarray:
        """Chunk i of a per-report host array, padded to chunk_size
        with dead lanes (row 0 repeated) — the single definition of
        the padding rule (device_chunk and the runner's key-schedule
        setup must pad identically)."""
        (lo, hi) = self.chunk_bounds(i)
        sl = x[lo:hi]
        pad = self.chunk_size - (hi - lo)
        if pad:
            sl = np.concatenate([sl, np.repeat(sl[:1], pad, axis=0)],
                                axis=0)
        return sl

    def device_chunk(self, i: int,
                     rows: Optional[int] = None
                     ) -> tuple[ReportBatch, np.ndarray]:
        """Chunk i as device arrays, padded to `rows` (default
        chunk_size) with dead lanes (row 0 repeated).  A mesh-sharded
        round passes rows = the next shard multiple of chunk_size so
        the padded tile places evenly across the report axis; the live
        mask excludes every padded lane either way.  Returns
        (batch, live mask)."""
        from ..backend.vidpf_jax import BatchedCorrectionWords

        if rows is None:
            rows = self.chunk_size
        (lo, hi) = self.chunk_bounds(i)

        def take(x):
            return None if x is None \
                else jnp.asarray(_pad_rows(self.host_slice(x, i), rows))

        a = self.arrays
        batch = ReportBatch(
            nonces=take(a["nonces"]),
            cws=BatchedCorrectionWords(
                seed=take(a["cws_seed"]), ctrl=take(a["cws_ctrl"]),
                w=take(a["cws_w"]), proof=take(a["cws_proof"])),
            keys=take(a["keys"]),
            leader_proofs=take(a["leader_proofs"]),
            helper_seeds=take(a["helper_seeds"]),
            leader_seeds=take(a["leader_seeds"]),
            peer_parts=tuple(take(p) for p in a["peer_parts"]))
        live = np.zeros(rows, bool)
        live[:hi - lo] = True
        return (batch, live)

    def host_bytes(self) -> int:
        total = 0
        for v in self.arrays.values():
            if isinstance(v, tuple):
                total += sum(x.nbytes for x in v if x is not None)
            elif v is not None:
                total += v.nbytes
        return total


def _pad_rows(x: np.ndarray, rows: int) -> np.ndarray:
    """Pad a per-report host array's leading axis to `rows` dead lanes
    (first row repeated — the same rule as HostReportStore.host_slice,
    so serial and mesh-padded tiles compute identical dead-lane data
    and the downloaded carries stay bit-identical after trimming)."""
    pad = rows - x.shape[0]
    if pad <= 0:
        return x
    return np.concatenate([x, np.repeat(x[:1], pad, axis=0)], axis=0)


class _ChunkState(NamedTuple):
    """One chunk's host-resident cross-round state: both aggregators'
    carries plus the per-report AES round keys (kept so rounds > 0
    skip the key-schedule recompute)."""
    carries: list   # [Carry-of-numpy x 2]
    ext_rk: np.ndarray
    conv_rk: np.ndarray


def _carry_to_host(carry):
    from ..backend.incremental import Carry

    return Carry(w=np.asarray(carry.w), proof=np.asarray(carry.proof),
                 seed=np.asarray(carry.seed),
                 ctrl=np.asarray(carry.ctrl))


def _carry_to_device(carry, rows: Optional[int] = None):
    from ..backend.incremental import Carry

    def up(x):
        return jnp.asarray(x if rows is None else _pad_rows(x, rows))

    return Carry(w=up(carry.w), proof=up(carry.proof),
                 seed=up(carry.seed), ctrl=up(carry.ctrl))


def _carry_trim(carry, rows: int):
    """Drop mesh-padding lanes from a downloaded host carry (inverse
    of _carry_to_device's pad; a no-op when nothing was padded)."""
    from ..backend.incremental import Carry

    if carry.w.shape[0] <= rows:
        return carry
    return Carry(w=carry.w[:rows], proof=carry.proof[:rows],
                 seed=carry.seed[:rows], ctrl=carry.ctrl[:rows])


def _carry_bytes(carry) -> int:
    # .nbytes is metadata on both np and jax arrays — never forces a
    # device->host transfer (np.asarray on a device carry would).
    return sum(x.nbytes for x in carry)


from .heavy_hitters import RoundPrograms


class ChunkedIncrementalRunner(RoundPrograms):
    """Drives backend/incremental.py chunk by chunk.

    External contract matches _IncrementalRunner (round(),
    width/fallback/layouts, checkpoint arrays), so
    HeavyHittersRun can swap it in when a chunk size is given; the
    jitted round programs are shared via RoundPrograms.
    """

    def __init__(self, bm: BatchedMastic, verify_key: bytes, ctx: bytes,
                 store: HostReportStore, reports: Optional[list] = None,
                 width: int = 8, n_device_shards: int = 1,
                 mesh=None):
        from ..backend.incremental import IncrementalMastic

        self.bm = bm
        self.verify_key = verify_key
        self.ctx = ctx
        self.store = store
        self.reports = reports
        self.num_reports = store.num_reports
        self.fallback = np.zeros(self.num_reports, bool)
        self.width = max(4, width)
        # A mesh given at construction shards every chunk's report
        # axis from round 0 (parallel/mesh.shard_incremental_runner
        # attaching one later is equivalent — the chunked runner's
        # cross-round state lives on the host, so there is nothing to
        # re-place).
        self.mesh = mesh
        if mesh is not None:
            n_device_shards = mesh.shape["reports"]
        self.n_device_shards = max(1, n_device_shards)
        check_envelope(bm, store.chunk_size, self.width,
                       self.num_reports, self.n_device_shards)
        self.engine = IncrementalMastic(bm, self.width)
        self._init_programs()
        # Warm artifact store (drivers/artifacts.py): preload the
        # first round's programs before anything compiles, so a
        # baked store makes construction + round 0 trace-free (the
        # key-schedule program below included); deeper levels
        # prefetch in the predictor's overlapped warm slot.
        self._preload_first_round(self._device_rows(),
                                  store.chunk_size)
        self.chunks = [self._init_chunk(i)
                       for i in range(store.num_chunks)]
        self.layouts: list = []  # per-depth creation layouts

    def _init_chunk(self, i: int) -> _ChunkState:
        """Initial carries and AES round keys for chunk i — built from
        cheap host slices (only the nonces cross to the device for the
        key schedules; uploading the whole chunk batch here would
        stream the full O(BITS) report store through the device,
        exactly the startup cost the chunked design avoids)."""
        nonces = self.store.host_slice(self.store.arrays["nonces"], i)
        keys = self.store.host_slice(self.store.arrays["keys"], i)
        nonce_dev = jnp.asarray(nonces)
        (rk_prog, _rk_wait) = self._rk_program(self.store.chunk_size,
                                               (nonce_dev,))
        (ext_rk, conv_rk) = rk_prog(nonce_dev)
        carries = [
            self.engine.init_carry(self.store.chunk_size, keys[:, a],
                                   a, host=True)
            for a in range(2)
        ]
        return _ChunkState(carries=carries,
                           ext_rk=np.asarray(ext_rk),
                           conv_rk=np.asarray(conv_rk))

    def _grow(self, width: int) -> None:
        from ..backend.incremental import Carry, IncrementalMastic

        n = (self.mesh.shape["reports"] if self.mesh is not None
             else self.n_device_shards)
        check_envelope(self.bm, self.store.chunk_size, width,
                       self.num_reports, n)
        pad = width - self.width
        for cs in self.chunks:
            for a in range(2):
                c = cs.carries[a]
                cs.carries[a] = Carry(
                    w=np.pad(c.w, ((0, 0), (0, 0), (0, pad),
                                   (0, 0), (0, 0))),
                    proof=np.pad(c.proof,
                                 ((0, 0), (0, 0), (0, pad), (0, 0))),
                    seed=np.pad(c.seed, ((0, 0), (0, pad), (0, 0))),
                    ctrl=np.pad(c.ctrl, ((0, 0), (0, pad))),
                )
        self.width = width
        self.engine = IncrementalMastic(self.bm, width)
        # AOT programs key on their shapes (the grown width maps to
        # fresh keys); only the jitted closures capture the engine.
        self._eval_fn = None
        self._combine_fn = None

    # -- one round over every chunk --------------------------------

    def _report_shards(self) -> int:
        """Report-axis device count this runner's chunks spread over
        (mesh wins over the construction-time hint; 1 = single chip).
        """
        return (self.mesh.shape["reports"] if self.mesh is not None
                else self.n_device_shards)

    def _device_rows(self) -> int:
        """Rows of one chunk's DEVICE tile: chunk_size padded up to
        the mesh's shard multiple (jax refuses uneven placement, so
        an uneven tail shards by padding + masking — the dead lanes
        are excluded from acceptance and aggregation exactly like the
        tail chunk's existing chunk_size padding)."""
        n = (self.mesh.shape["reports"] if self.mesh is not None
             else 1)
        return -(-self.store.chunk_size // n) * n

    def _resident_dev_bytes(self) -> int:
        """One device tile's resident bytes at the padded row count
        (the measured per-chunk accounting scaled from chunk_size to
        the mesh-padded rows)."""
        acct = self.memory_accounting()["device_bytes_per_chunk"]
        return acct * self._device_rows() // self.store.chunk_size

    def _carry_dev_bytes(self) -> int:
        """The carries' share of `_resident_dev_bytes`."""
        acct = self.memory_accounting()["device_carry_bytes"]
        return acct * self._device_rows() // self.store.chunk_size

    def _pipeline_mode(self, plan) -> tuple:
        """(mode, fallback_reason): whether this round runs the
        double-buffered executor or degrades to serial — and why, so
        the fallback is named in metrics, never silent.  Mesh-sharded
        rounds pipeline like single-chip ones (the r10 tentpole); the
        budget term prices the PER-SHARD doubled footprint."""
        from .pipeline import pipeline_enabled

        if not pipeline_enabled():
            return ("serial", "lever-off")
        if self.store.num_chunks < 2:
            return ("serial", "single-chunk")
        budget = _device_budget()
        if budget > 0:
            peak = round_peak_bytes(
                self.bm, len(plan.onehot_idx),
                len(plan.payload_parent), self._device_rows(),
                self._resident_dev_bytes(),
                self._report_shards(),
                chunks_in_flight=PIPELINE_CHUNKS_IN_FLIGHT,
                carry_bytes=self._carry_dev_bytes())
            if peak > budget:
                return ("serial", "device-budget")
        return ("pipelined", None)

    def round(self, agg_param,
              metrics_out: Optional[list] = None) -> list:
        """One round over every chunk on the pipelined executor
        (drivers/pipeline.py): chunk i+1's batch and carries upload
        and its whole eval -> weight-check -> mask-combine ->
        aggregate chain dispatches while chunk i computes and its
        result carries download — one blocking host sync per chunk,
        issued after the next chunk's work is in flight.  The
        accept/ok/weight-check masks combine ON DEVICE (exactly the
        serial boolean algebra, so aggregates are bit-identical),
        and the per-chunk phase timeline lands in
        `RoundMetrics.extra`.  Degrades to serial (same stage/collect
        bodies, no overlap) when the doubled in-flight footprint
        exceeds the device budget — the fallback is named in
        metrics."""
        from ..backend.incremental import round_inputs
        from .heavy_hitters import _vk_array, splice_rejected
        from .pipeline import overlap_efficiency, run_chunks

        (level, prefixes, do_weight_check) = agg_param
        plan = self._plan(prefixes, level)
        shards = self._report_shards()
        dev_rows = self._device_rows()
        check_round_peak(
            self.bm,
            len(plan.onehot_idx), len(plan.payload_parent),
            dev_rows, self._resident_dev_bytes(), level, shards,
            self._carry_dev_bytes())
        (mode, fb_reason) = self._pipeline_mode(plan)
        rnd = round_inputs(plan)
        vk_arr = _vk_array(self.verify_key)
        ones = jnp.ones(dev_rows, bool)
        if self.mesh is not None:
            # Small per-round inputs replicate across the mesh, the
            # per-report ones mask shards — pinned explicitly so the
            # warm-compiled sharded programs see exactly these
            # shardings at dispatch (heavy_hitters.RoundPrograms).
            from ..parallel.mesh import place_replicated, place_reports
            (rnd, vk_arr) = place_replicated(self.mesh, (rnd, vk_arr))
            ones = place_reports(self.mesh, ones)
        rows = len(prefixes) * (1 + self.bm.m.flp.OUTPUT_LEN)
        chunk_size = self.store.chunk_size

        agg_shares = [[self.bm.m.field(0)] * rows for _ in range(2)]
        accept_all = np.zeros(self.num_reports, bool)
        # Per-check masks across chunks, so rejection attribution
        # matches the resident runner's (first-failing-check order).
        eval_ok_all = np.zeros(self.num_reports, bool)
        wc_ok_all = (np.zeros(self.num_reports, bool)
                     if do_weight_check else None)
        jr_ok_all: Optional[np.ndarray] = None
        warm_args: list = [None]
        warm_spent: list = [0.0]
        psum_bytes: list = [0]
        shard_skews: dict = {}

        def stage(i: int):
            """Upload chunk i and dispatch its full device chain —
            returns futures only, no blocking sync."""
            cs = self.chunks[i]
            t0 = time.perf_counter()
            (batch, live) = self.store.device_chunk(i, rows=dev_rows)
            (lo, hi) = self.store.chunk_bounds(i)
            # The aggregation validity mask, known at stage time: live
            # (non-padding) lanes whose device carry was intact BEFORE
            # this round.  This round's ok / wc_ok fold in on device,
            # reproducing the serial path's fallback-then-mask order.
            valid = live.copy()
            valid[:hi - lo] &= ~self.fallback[lo:hi]
            dev_c0 = _carry_to_device(cs.carries[0], dev_rows)
            dev_c1 = _carry_to_device(cs.carries[1], dev_rows)
            ext_rk = jnp.asarray(_pad_rows(cs.ext_rk, dev_rows))
            conv_rk = jnp.asarray(_pad_rows(cs.conv_rk, dev_rows))
            valid_dev = jnp.asarray(valid)
            if self.mesh is not None:
                # Chunk upload lands report-sharded across the mesh;
                # aggregation below is the only cross-chip collective.
                from ..parallel.mesh import place_reports
                (batch, dev_c0, dev_c1, ext_rk, conv_rk, valid_dev) = \
                    place_reports(self.mesh,
                                  (batch, dev_c0, dev_c1, ext_rk,
                                   conv_rk, valid_dev))
            t_up = time.perf_counter()
            args = (vk_arr, dev_c0, dev_c1, rnd, ext_rk, conv_rk,
                    batch.cws)
            (eval_prog, compile_s) = self._eval_program(
                dev_rows, plan, args)
            t_d0 = time.perf_counter()
            (c0, c1, out0, out1, accept_ev, ok) = eval_prog(*args)
            wc_checks = {}
            wc_compile_s = 0.0
            (wc_accept, wc_okdev, jr) = (ones, ones, ones)
            if do_weight_check:
                wcargs = (vk_arr, batch, c0.w[:, 0, :2],
                          c1.w[:, 0, :2])
                (wc_prog, wc_compile_s) = self._wc_program(
                    dev_rows, level, wcargs)
                (wc_checks, wc_okdev) = wc_prog(*wcargs)
                wc_accept = wc_checks["weight_check"]
                jr = wc_checks.get("joint_rand", ones)
            cargs = (out0, out1, accept_ev, ok, valid_dev,
                     wc_accept, wc_okdev, jr)
            (agg_prog, agg_compile_s) = self._agg_program(
                dev_rows, cargs)
            (accept_dev, agg0, agg1) = agg_prog(*cargs)
            t_d1 = time.perf_counter()
            if warm_args[0] is None:
                warm_args[0] = args  # shape template for _warm_next
            compile_ms = (compile_s + agg_compile_s
                          + wc_compile_s) * 1e3
            phases = {
                "upload_ms": round((t_up - t0) * 1e3, 3),
                "compile_ms": round(compile_ms, 3),
                "dispatch_ms": round(
                    (t_d1 - t_d0 - compile_s - agg_compile_s
                     - wc_compile_s) * 1e3, 3),
            }
            handle = (c0, c1, accept_ev, ok, wc_checks, wc_okdev,
                      accept_dev, agg0, agg1)
            return (handle, phases)

        def collect(i: int, handle) -> dict:
            """Chunk i's single blocking sync, downloads, host fold."""
            (c0, c1, accept_ev, ok, wc_checks, wc_okdev,
             accept_dev, agg0, agg1) = handle
            cs = self.chunks[i]
            (lo, hi) = self.store.chunk_bounds(i)
            t0 = time.perf_counter()
            if self.mesh is not None and shards > 1:
                # Per-shard completion skew, measured inside the
                # chunk's one sync window: block the report-sharded
                # accept mask shard by shard (device order) before the
                # global sync — the straggler shard shows up as the
                # max-min spread.  Observability only; the arithmetic
                # never depends on it.
                waits = []
                for sh in accept_dev.addressable_shards:
                    sh.data.block_until_ready()
                    waits.append((time.perf_counter() - t0) * 1e3)
                shard_skews[i] = round(max(waits) - min(waits), 3)
                # One psum per aggregator's replicated aggregate.
                psum_bytes[0] += agg0.nbytes + agg1.nbytes
            jax.block_until_ready(
                (c0, c1, accept_ev, ok, wc_checks, wc_okdev,
                 accept_dev, agg0, agg1))
            t_wait = time.perf_counter()
            cs.carries[0] = _carry_trim(_carry_to_host(c0), chunk_size)
            cs.carries[1] = _carry_trim(_carry_to_host(c1), chunk_size)
            ok_np = np.asarray(ok)
            accept_ev_np = np.asarray(accept_ev)
            accept_np = np.asarray(accept_dev)
            agg_np = [np.asarray(agg0), np.asarray(agg1)]
            wc_np = {k: np.asarray(v) for (k, v) in wc_checks.items()}
            wc_ok_np = (np.asarray(wc_okdev) if do_weight_check
                        else None)
            t_down = time.perf_counter()
            self.fallback[lo:hi] |= ~ok_np[:hi - lo]
            eval_ok_all[lo:hi] = accept_ev_np[:hi - lo]
            if do_weight_check:
                self.fallback[lo:hi] |= ~wc_ok_np[:hi - lo]
                wc_ok_all[lo:hi] = wc_np["weight_check"][:hi - lo]
                if "joint_rand" in wc_np:
                    nonlocal jr_ok_all
                    if jr_ok_all is None:
                        jr_ok_all = np.zeros(self.num_reports, bool)
                    jr_ok_all[lo:hi] = wc_np["joint_rand"][:hi - lo]
            for a in range(2):
                agg_shares[a] = vec_add(
                    agg_shares[a],
                    self.bm.agg_share_to_host(agg_np[a][:rows]))
            accept_all[lo:hi] = accept_np[:hi - lo]
            t_host = time.perf_counter()
            return {
                "compute_wait_ms": round((t_wait - t0) * 1e3, 3),
                "download_ms": round((t_down - t_wait) * 1e3, 3),
                "host_ms": round((t_host - t_down) * 1e3, 3),
            }

        def warm_predicted() -> None:
            # Every chunk's device work is dispatched and the host is
            # about to idle in the final blocking sync: compile the
            # predicted next level's programs while the device
            # computes through them (see pipeline.ProgramCache for
            # why this is synchronous, not a compiler thread).
            warm_spent[0] = self._warm_next(plan, warm_args[0],
                                            dev_rows)

        from .pipeline import paused_gc
        with paused_gc():
            # GC paused for the chunk loop: its traces (first-call
            # jits, inline lowers) segfault this jaxlib if a
            # collection fires mid-trace (pipeline.paused_gc).
            (timeline, wall_ms) = run_chunks(
                self.store.num_chunks, stage, collect,
                pipelined=(mode == "pipelined"),
                before_last_collect=warm_predicted)

        evals_per_report = 2 * plan.parent_count * 2  # both parties
        for rec in timeline:
            (lo, hi) = self.store.chunk_bounds(rec["chunk"])
            span_s = max(rec["collect_end_ms"]
                         - rec["stage_start_ms"], 1e-3) / 1e3
            rec["reports"] = hi - lo
            rec["wall_ms"] = round(span_s * 1e3, 2)
            # Live-report rate (comparable across full and partial
            # chunks) AND the padded device-work rate — the tail chunk
            # computes dev_rows padded lanes but only hi-lo of them
            # are reports, so a single padded-rate stamp would
            # overstate tail throughput (r9's honesty fix, extended
            # to the mesh's shard-multiple padding).
            rec["node_evals_per_sec"] = round(
                (hi - lo) * evals_per_report / span_s, 1)
            rec["node_evals_per_sec_padded"] = round(
                dev_rows * evals_per_report / span_s, 1)
            if self.mesh is not None:
                # Per-shard twins of both stamps: each chip computes
                # dev_rows/shards lanes of the chunk, so the per-shard
                # rate is the number the single-chip roofline compares
                # against (PERF.md §8).
                rec["node_evals_per_sec_per_shard"] = round(
                    rec["node_evals_per_sec"] / shards, 1)
                rec["node_evals_per_sec_padded_per_shard"] = round(
                    rec["node_evals_per_sec_padded"] / shards, 1)
                if rec["chunk"] in shard_skews:
                    rec["shard_wait_skew_ms"] = \
                        shard_skews[rec["chunk"]]
        chunk_stats = timeline

        assert level == len(self.layouts)
        self.layouts.append(plan.layout_new)

        metrics = RoundMetrics(level=level,
                               frontier_width=len(prefixes),
                               padded_width=self.width,
                               reports_total=self.num_reports)
        attribute_rejections(metrics, eval_ok_all, wc_ok_all,
                             jr_ok_all, device_ok=~self.fallback)
        count_round_ops(metrics, self.bm.m, self.num_reports,
                        2 * plan.parent_count,
                        include_key_setup=(level == 0))
        count_round_bytes(metrics, self.bm.m, agg_param,
                          self.num_reports)
        metrics.extra["chunks"] = chunk_stats
        metrics.extra["memory"] = self.memory_accounting()
        compile_inline_ms = sum(rec["phases"].get("compile_ms", 0.0)
                                for rec in timeline)
        metrics.extra["pipeline"] = {
            "mode": mode,
            "fallback": fb_reason,
            "round_wall_ms": round(wall_ms, 2),
            "overlap_efficiency": overlap_efficiency(timeline,
                                                     wall_ms),
            "compile_inline_ms": round(compile_inline_ms, 2),
            "warm_ms": round(warm_spent[0] * 1e3, 2),
            # One blocking sync per chunk (the executor contract) —
            # stamped here too so the pipeline block carries the same
            # key set as the resident producer (obs/schema.py).
            "host_syncs": sum(rec["host_syncs"] for rec in timeline),
            "aot": self._aot_summary(dev_rows, plan,
                                     compile_inline_ms),
        }
        metrics.extra["artifacts"] = self._artifacts_block()
        if self.mesh is not None:
            # Collective overhead made observable (not inferred): one
            # psum of each aggregator's O(frontier) aggregate share
            # per chunk is the round's ONLY cross-chip traffic.
            skews = sorted(shard_skews.values())
            metrics.extra["mesh"] = {
                "report_shards": shards,
                "device_rows_per_chunk": dev_rows,
                "rows_per_shard": dev_rows // shards,
                "psum_bytes_per_round": psum_bytes[0],
                "shard_wait_skew_ms_p50":
                    (skews[len(skews) // 2] if skews else 0.0),
                "shard_wait_skew_ms_max":
                    (skews[-1] if skews else 0.0),
            }

        splice_rejected(self.bm.m, self.verify_key, self.ctx, agg_param,
                        self.reports, ~self.fallback, accept_all,
                        agg_shares)
        metrics.accepted = int(accept_all.sum())
        metrics.xof_fallbacks = int(self.fallback.sum())
        metrics.rejected_fallback = int(
            (self.fallback & ~accept_all).sum())
        if metrics_out is not None:
            metrics_out.append(metrics)
        num = int(accept_all.sum())
        return self.bm.m.unshard(agg_param, agg_shares, num)

    def memory_accounting(self) -> dict:
        """Device-vs-host footprint: the chunked design's reason to
        exist.  Device holds one chunk (2 carries + batch tile); host
        holds every chunk's carry plus the report store."""
        carry = 2 * _carry_bytes(self.chunks[0].carries[0])
        rk = (self.chunks[0].ext_rk.nbytes
              + self.chunks[0].conv_rk.nbytes)
        store = self.store
        tile = 0
        for v in store.arrays.values():
            if isinstance(v, tuple):
                tile += sum(x[:1].nbytes * store.chunk_size
                            for x in v if x is not None)
            elif v is not None:
                tile += v[:1].nbytes * store.chunk_size
        host = (sum(2 * _carry_bytes(cs.carries[0]) + cs.ext_rk.nbytes
                    + cs.conv_rk.nbytes for cs in self.chunks)
                + store.host_bytes())
        return {
            "chunk_size": store.chunk_size,
            "num_chunks": store.num_chunks,
            "device_bytes_per_chunk": carry + rk + tile,
            "device_carry_bytes": carry,
            "host_bytes_total": host,
        }

    # -- checkpoint hooks (HeavyHittersRun.to_bytes/from_bytes) ----

    def state_arrays(self) -> dict:
        from ..backend.incremental import carry_to_arrays

        data: dict = {"chunk_size": np.int64(self.store.chunk_size)}
        for (i, cs) in enumerate(self.chunks):
            data.update(carry_to_arrays(cs.carries[0], f"k{i}_c0_"))
            data.update(carry_to_arrays(cs.carries[1], f"k{i}_c1_"))
        return data

    def load_state(self, arrays, num_chunks: int) -> None:
        from ..backend.incremental import carry_from_arrays

        for i in range(num_chunks):
            self.chunks[i].carries[0] = _carry_to_host(
                carry_from_arrays(arrays, f"k{i}_c0_"))
            self.chunks[i].carries[1] = _carry_to_host(
                carry_from_arrays(arrays, f"k{i}_c1_"))

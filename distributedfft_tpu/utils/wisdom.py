"""Persistent plan "wisdom": autotune once, reuse everywhere.

FFTW saves its planner measurements as *wisdom*; XLA amortizes compilation
through its persistent compilation cache (bench.py wires it). This module is
the same amortization for THIS framework's two measured plan choices:

* the local-FFT backend race (``testing/autotune.autotune_local_fft`` —
  a measured 3.3x spread between backends on v5e, see its docstring), and
* the comm-variant race (``testing/autotune.autotune_comm`` with
  ``race_send=True`` — comm_method x send_method x opt x streams-chunks,
  the reference's primary comparative dimension, plus the RING
  ppermute-ring rendering added in store version 2).

The reference pays its tuning once per plan (``cufftMakePlanMany64`` picks
kernels at plan creation); our port previously re-raced on every process
start. With wisdom, ``Config(fft_backend="auto")`` / ``Config(comm_method=
"auto")`` plans consult the store at construction, race-and-record on a
miss (bounded iterations, accuracy-gated exactly like the underlying
autotuners), and reuse silently on a hit — steady-state plan creation costs
zero measurement time.

Store format: ONE JSON file::

    {"version": 5,
     "entries": {"<canonical key json>": {"local_fft": {...}, "comm": {...},
                                          "wire": {...}}}}

Keys fold in everything that can change a winner: platform, device kind,
jax version, global shape, dtype, mesh shape, decomposition (kind +
partition grid + sequence/variant + transform), and norm. A key built on a
different mesh, dtype or jax version simply misses.

Version 2 added the RING (ppermute-ring) rendering to the comm race.
Version 3 added the WIRE axis: ``comm`` records gained ``wire_dtype``
(the comm race crosses every cell with the bf16 compressed-wire twin,
error-budget-gated), and the ``wire`` slot records the wire-only race run
for ``Config(wire_dtype="auto")`` with an explicit comm method.
Version 4 added the RING_OVERLAP (double-buffered ring) rendering to the
comm race (ISSUE 10).
Version 5 added the overlap-schedule axes (ISSUE 16): ``comm`` records
carry ``overlap_depth``/``overlap_subblocks`` — the revolving-buffer ring
depth and the per-peer sub-block split the race crossed into the ring and
pipelined-all-to-all candidates (``None`` = the axis was not raced for
that winner, same never-clobber contract as an unraced ``wire``). Legacy
stores MIGRATE rather than error: ``local_fft``/``wire`` (and any other
non-``comm``) records are agnostic to the comm-race axes and carry over
verbatim, while older ``comm`` records were winners of races that never
saw the ring (v1), wire (v1/v2), overlap (v1-v3) or depth/sub-block
(v1-v4) axis and therefore read as misses (re-raced once, re-recorded
under v5). Any later/unknown version reads as empty.

Degradation contract: a missing, corrupt, partially-valid or
version-mismatched store reads as EMPTY (re-measure); a record whose fields
no longer validate (e.g. a backend this build doesn't know) is a miss; a
failed write is swallowed after a best-effort retry. Wisdom can cost a
redundant measurement, never an error. Writes are atomic (tmp +
``os.replace``), merge from a fresh read of the on-disk entries, and the
read-merge-replace window is serialized across processes by a best-effort
advisory lock on ``<path>.lock`` (``fcntl.flock``) — so N concurrent
recorders sharing one ``$DFFT_WISDOM`` cannot interleave into a corrupt
store or lose each other's updates. Where flock is unavailable the write
stays atomic but unlocked: a concurrent update can then be lost (and is
simply re-measured by a later miss; wisdom loses measurements, never
correctness).

The store path resolves as ``Config.wisdom_path`` -> ``$DFFT_WISDOM`` ->
disabled. ``Config(use_wisdom=False)`` (CLI ``--no-wisdom``) never touches
disk; "auto" then races per process like before wisdom existed.
"""

from __future__ import annotations

import contextlib
import json
import os
import tempfile
import time
from typing import Any, Dict, Iterator, Optional, Sequence, Tuple

try:
    from .. import obs
except ImportError:
    # Standalone load (tests exercise the advisory-lock contract by
    # exec'ing this file without the package): observability degrades to
    # no-ops, exactly like every other wisdom failure mode.
    import contextlib as _contextlib

    class _NullObs:  # noqa: D401 — minimal stand-in
        class metrics:
            @staticmethod
            def inc(name: str, n: int = 1) -> None:
                pass

            @staticmethod
            def gauge(name: str, value: Any) -> None:
                pass

        @staticmethod
        def span(name: str, **attrs: Any) -> Any:
            return _contextlib.nullcontext()

        @staticmethod
        def event(name: str, **attrs: Any) -> None:
            pass

        @staticmethod
        def notice(msg: str, **attrs: Any) -> None:
            pass

    obs = _NullObs()

try:
    from ..resilience import inject as _inject
except ImportError:
    # Standalone load (see the obs fallback above): injection degrades to
    # inactive, like every other wisdom failure mode.
    class _inject:  # noqa: D401 — minimal stand-in
        @staticmethod
        def lock_contended() -> bool:
            return False

WISDOM_VERSION = 5
# Store versions that migrate on load instead of reading empty (their
# non-"comm" slots carry over; see _migrate_legacy).
_LEGACY_VERSIONS = (1, 2, 3, 4)
ENV_VAR = "DFFT_WISDOM"
# Wire dtypes a stored record may carry (the "auto" marker never lands on
# disk — records hold measured winners).
_WIRE_CONCRETE = ("native", "bf16")

# Bounded construction-time race defaults. The local chain length is the
# floor that still cancels dispatch noise on CPU-class timers; raise
# DFFT_WISDOM_K where the per-call constant is larger (chaintimer
# docstring).
_RACE_REPEATS = 2
_RACE_INNER = 2
_COMM_ITERATIONS = 3
_COMM_WARMUP = 1
_FALLBACK_BACKEND = "xla"  # when every candidate fails the gate

# (path, legacy version) pairs already reported: load() runs on every
# lookup/record, and one store must announce its migration once, not per
# consult.
_MIGRATION_SEEN = set()


def _note_migration(path: str, version: int) -> None:
    key = (path, int(version))
    if key in _MIGRATION_SEEN:
        return
    _MIGRATION_SEEN.add(key)
    obs.metrics.inc("wisdom.migrations")
    obs.notice(
        f"wisdom: migrated(v{version}→v{WISDOM_VERSION}) {path} "
        f"(local_fft carries over; comm records re-race as misses)",
        name="wisdom.migration", path=path, from_version=int(version),
        to_version=WISDOM_VERSION)


def _race_k() -> int:
    try:
        return max(2, int(os.environ.get("DFFT_WISDOM_K", "17")))
    except ValueError:
        return 17


def default_path() -> Optional[str]:
    """Store path from ``$DFFT_WISDOM`` (empty/unset -> wisdom disabled)."""
    p = os.environ.get(ENV_VAR, "").strip()
    return p or None


def open_store(path: Optional[str] = None,
               enabled: bool = True) -> Optional["WisdomStore"]:
    """A store for an explicit path (or the env default), or None when
    disabled / no path is configured."""
    if not enabled:
        return None
    p = path or default_path()
    return WisdomStore(p) if p else None


def store_for_config(config: Any) -> Optional["WisdomStore"]:
    """The store a Config selects (``wisdom_path``/``use_wisdom`` fields)."""
    return open_store(getattr(config, "wisdom_path", None),
                      getattr(config, "use_wisdom", True))


def _lock_timeout_s() -> float:
    try:
        return float(os.environ.get("DFFT_WISDOM_LOCK_TIMEOUT_S", "10"))
    except ValueError:
        return 10.0


def _lock_stale_s() -> float:
    try:
        return float(os.environ.get("DFFT_WISDOM_LOCK_STALE_S", "60"))
    except ValueError:
        return 60.0


@contextlib.contextmanager
def _advisory_lock(path: str) -> Iterator[None]:
    """Best-effort exclusive ``fcntl.flock`` on ``path + '.lock'``,
    serializing the read-merge-replace window across processes sharing one
    store — with BOUNDED acquisition (resilience leg 4): the old blocking
    ``LOCK_EX`` would wait FOREVER on a holder that hung mid-window
    (suspended process, dead NFS client holding the lease), wedging every
    later recorder. Now the lock is polled non-blocking up to
    ``$DFFT_WISDOM_LOCK_TIMEOUT_S`` (default 10 s):

    * a holder that DIED outright is harmless — the kernel releases its
      flock with the fd, and the leftover ``.lock`` FILE is reused, never
      treated as held (pinned by tests/test_resilience.py's kill-the-
      holder regression);
    * a holder still ALIVE but hung is detected by age: when the lock
      file's mtime (touched on every acquisition) is older than
      ``$DFFT_WISDOM_LOCK_STALE_S`` (default 60 s), the lock file is
      BROKEN once — unlinked and re-created, so the hung holder keeps its
      flock on the orphaned inode while new recorders serialize on the
      fresh one (``wisdom.lock_breaks`` metric + notice);
    * past the timeout the writer proceeds UNLOCKED
      (``wisdom.lock_timeouts``): the write itself stays atomic (tmp +
      ``os.replace``), so a concurrent update can be lost — wisdom loses
      measurements, never correctness, and never hangs.

    Degrades to unlocked on platforms/filesystems without flock, exactly
    as before. ``$DFFT_FAULT_SPEC=wisdom:stale-lock`` simulates the hung
    holder (``resilience/inject.py``) so CI exercises these paths."""
    lock_path = path + ".lock"
    lock = None
    try:
        try:
            import fcntl
        except ImportError:
            fcntl = None
        if fcntl is not None:
            deadline = time.monotonic() + _lock_timeout_s()
            delay, broke = 0.005, False
            while True:
                try:
                    lock = open(lock_path, "a")
                    if _inject.lock_contended():
                        raise BlockingIOError("injected: lock held by a "
                                              "hung holder")
                    fcntl.flock(lock, fcntl.LOCK_EX | fcntl.LOCK_NB)
                    try:
                        os.utime(lock_path)  # acquisition stamp (age base)
                    except OSError:
                        pass
                    break  # acquired
                except BlockingIOError:
                    # Genuinely held by another process: stale-break once,
                    # else poll until the acquisition deadline.
                    if lock is not None:
                        lock.close()
                        lock = None
                    try:
                        age = time.time() - os.path.getmtime(lock_path)
                    except OSError:
                        age = 0.0
                    if not broke and age > _lock_stale_s():
                        broke = True
                        try:
                            os.unlink(lock_path)
                        except OSError:
                            pass
                        obs.metrics.inc("wisdom.lock_breaks")
                        obs.notice(
                            f"wisdom: broke stale lock {lock_path} "
                            f"(age {age:.0f}s > {_lock_stale_s():.0f}s)",
                            name="wisdom.lock_break", path=lock_path,
                            age_s=round(age, 1))
                        continue
                    if time.monotonic() >= deadline:
                        obs.metrics.inc("wisdom.lock_timeouts")
                        obs.notice(
                            f"wisdom: lock {lock_path} not acquired within "
                            f"{_lock_timeout_s():.0f}s; writing unlocked "
                            "(atomic replace; a concurrent update may be "
                            "lost, never corrupted)",
                            name="wisdom.lock_timeout", path=lock_path)
                        break  # proceed unlocked
                    time.sleep(delay)
                    delay = min(0.1, delay * 2)
                except OSError:
                    # Not contention: flock unsupported on this filesystem
                    # (ENOTSUP) or the lock path unwritable. Degrade to
                    # unlocked IMMEDIATELY, exactly like the pre-timeout
                    # code — polling would stall every write for the full
                    # timeout on a platform that can never acquire.
                    if lock is not None:
                        lock.close()
                        lock = None
                    break
        yield
    finally:
        if lock is not None:
            try:
                import fcntl
                fcntl.flock(lock, fcntl.LOCK_UN)
            except (ImportError, OSError, ValueError):
                pass
            lock.close()


class WisdomStore:
    """One JSON wisdom file; every read is tolerant, every write atomic
    (and advisory-locked against concurrent recorders)."""

    def __init__(self, path: str) -> None:
        self.path = os.path.expanduser(str(path))

    # -- raw I/O -----------------------------------------------------------

    @staticmethod
    def _empty() -> Dict[str, Any]:
        return {"version": WISDOM_VERSION, "entries": {}}

    @staticmethod
    def _migrate_legacy(raw: Dict[str, Any]) -> Dict[str, Any]:
        """Legacy (v1-v4) store -> version-5 view: ``local_fft``/``wire``
        (and any other non-``comm``) records are agnostic to the
        comm-race axes and carry over; ``comm`` records predate an axis
        of the race (the RING rendering for v1, the wire dtype for v1/v2,
        the RING_OVERLAP rendering for v1-v3, the overlap depth/sub-block
        axes for v1-v4) and are dropped, so they re-measure as ordinary
        misses. Persisted as v5 by the next ``record``."""
        entries = {}
        for k, e in raw["entries"].items():
            if not isinstance(e, dict):
                continue
            kept = {s: r for s, r in e.items() if s != "comm"}
            if kept:
                entries[k] = kept
        return {"version": WISDOM_VERSION, "entries": entries}

    def load(self) -> Dict[str, Any]:
        """Parsed store; ANY defect (missing file, malformed JSON, wrong
        schema, unknown version) degrades to the empty store. A legacy
        (v1-v4) store migrates (see ``_migrate_legacy``) instead of
        reading empty."""
        with obs.span("wisdom.load", path=self.path):
            try:
                with open(self.path, "r", encoding="utf-8") as f:
                    raw = json.load(f)
            except (OSError, ValueError):
                return self._empty()
            if (not isinstance(raw, dict)
                    or not isinstance(raw.get("entries"), dict)):
                return self._empty()
            if raw.get("version") in _LEGACY_VERSIONS:
                _note_migration(self.path, raw["version"])
                return self._migrate_legacy(raw)
            if raw.get("version") != WISDOM_VERSION:
                return self._empty()
            return raw

    def raw_version(self) -> Optional[int]:
        """The on-disk schema version (before migration), or None when the
        file is missing/unreadable — what ``dfft-explain`` reports as the
        store's provenance."""
        try:
            with open(self.path, "r", encoding="utf-8") as f:
                raw = json.load(f)
        except (OSError, ValueError):
            return None
        v = raw.get("version") if isinstance(raw, dict) else None
        return v if isinstance(v, int) else None

    def lookup(self, key: str, slot: str) -> Optional[Dict[str, Any]]:
        """The recorded dict under ``entries[key][slot]``, or None."""
        entry = self.load()["entries"].get(key)
        if not isinstance(entry, dict):
            return None
        rec = entry.get(slot)
        return rec if isinstance(rec, dict) else None

    def record(self, key: str, slot: str, rec: Dict[str, Any]) -> bool:
        """Merge ``rec`` into the on-disk store atomically, holding the
        advisory lock across the read-merge-replace window so concurrent
        recorders serialize instead of losing each other's updates.
        Best-effort: returns False (never raises) when the write cannot
        land. Records are stamped with ``recorded_at`` (UTC ISO-8601) so
        provenance surfaces (``dfft-explain``) can say WHEN a winner was
        measured; readers tolerate the extra key."""
        rec = dict(rec)
        rec.setdefault("recorded_at",
                       time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()))
        try:
            d = os.path.dirname(os.path.abspath(self.path))
            os.makedirs(d, exist_ok=True)
            with obs.span("wisdom.record", path=self.path, slot=slot), \
                    _advisory_lock(self.path):
                data = self.load()  # re-read: merge with concurrent writers
                entry = data["entries"].setdefault(key, {})
                if not isinstance(entry, dict):  # damaged entry: replace
                    entry = data["entries"][key] = {}
                entry[slot] = rec
                fd, tmp = tempfile.mkstemp(prefix=".wisdom.", dir=d)
                try:
                    with os.fdopen(fd, "w", encoding="utf-8") as f:
                        json.dump(data, f, indent=1, sort_keys=True)
                    os.replace(tmp, self.path)
                finally:
                    if os.path.exists(tmp):
                        try:
                            os.unlink(tmp)
                        except OSError:
                            pass
            return True
        except (OSError, TypeError, ValueError):
            return False


# ---------------------------------------------------------------------------
# keys
# ---------------------------------------------------------------------------

def _device_fingerprint() -> Dict[str, str]:
    import jax
    d = jax.devices()[0]
    return {"platform": str(d.platform),
            "device_kind": str(getattr(d, "device_kind", d.platform)),
            "jax": jax.__version__}


def _decomp_desc(kind: str, partition: Any, sequence: Any = None,
                 variant: Optional[str] = None) -> str:
    from .. import params as pm
    if isinstance(partition, pm.PencilPartition):
        grid = f"{partition.p1}x{partition.p2}"
    else:
        grid = str(partition.num_ranks)
    desc = f"{kind}:{grid}"
    if sequence is not None:
        desc += f":{pm.SlabSequence.parse(sequence).value}"
    if variant:
        desc += f":{variant}"
    return desc


def plan_key(kind: str, global_shape: Sequence[int], double_prec: bool,
             partition: Any, norm: Any, transform: str = "r2c",
             sequence: Any = None,
             variant: Optional[str] = None,
             mesh_shape: Optional[Dict[str, int]] = None,
             dims: int = 3) -> str:
    """Canonical store key for one plan configuration: platform, device
    kind, jax version, global shape, dtype, mesh shape, decomposition,
    norm (+ transform and partial-transform depth ``dims`` — a pencil
    ``--fft-dim 2`` race times a transpose-1-only program, so its winner
    must not be reused by a full-3D plan). ``mesh_shape`` defaults to the
    mesh the partition itself determines, so recorders without a mesh in
    hand (the CLIs) and plan-construction lookups build the same key."""
    parts = dict(_device_fingerprint())
    parts.update({
        "shape": list(int(s) for s in global_shape),
        "dtype": "f64" if double_prec else "f32",
        "mesh": (mesh_shape if mesh_shape is not None
                 else _mesh_shape_of(None, partition)),
        "decomp": _decomp_desc(kind, partition, sequence, variant),
        "norm": getattr(norm, "value", str(norm)),
        "transform": transform,
        "dims": int(dims),
    })
    return json.dumps(parts, sort_keys=True, separators=(",", ":"))


def local_key(shape: Sequence[int], double_prec: bool) -> str:
    """Key for a bare single-device local-FFT race (no plan around it):
    what ``dfft-reference --autotune`` records and bench.py warm-starts
    from."""
    parts = dict(_device_fingerprint())
    parts.update({"shape": list(int(s) for s in shape),
                  "dtype": "f64" if double_prec else "f32",
                  "decomp": "local-fft", "mesh": {}})
    return json.dumps(parts, sort_keys=True, separators=(",", ":"))


def _mesh_shape_of(mesh: Any, partition: Any) -> Dict[str, int]:
    if mesh is not None:
        return {str(k): int(v) for k, v in dict(mesh.shape).items()}
    # The mesh a plan WILL build is fully determined by the partition.
    from .. import params as pm
    from ..parallel.mesh import PENCIL_AXES, SLAB_AXIS
    if isinstance(partition, pm.PencilPartition):
        return {PENCIL_AXES[0]: partition.p1, PENCIL_AXES[1]: partition.p2}
    if partition.num_ranks > 1:
        return {SLAB_AXIS: partition.num_ranks}
    return {}


# ---------------------------------------------------------------------------
# record helpers (shared by resolution, the CLIs and bench.py)
# ---------------------------------------------------------------------------

def local_fft_record(candidate: Any) -> Dict[str, Any]:
    """Serialize a winning ``autotune.Candidate``."""
    import numpy as np
    rec = {"fft_backend": candidate.backend,
           "mxu_precision": candidate.precision,
           "mxu_direct_max": candidate.direct_max}
    if np.isfinite(candidate.per_iter_ms):
        rec["per_iter_ms"] = round(float(candidate.per_iter_ms), 4)
    if np.isfinite(candidate.rel_err):
        rec["rel_err"] = float(f"{candidate.rel_err:.3e}")
    return rec


def comm_record(candidate: Any, base_config: Any = None) -> Dict[str, Any]:
    """Serialize a winning ``autotune.CommCandidate``. ``send=None``
    candidates were timed with the BASE config's send method; pass the base
    that was actually raced (``base_config``) so a non-SYNC base (the CLI
    ``--autotune-comm -snd Streams`` case) is recorded as the send method
    the measurement really used — a later "auto" fold must reproduce the
    timed program, not silently swap in SYNC."""
    import numpy as np

    from .. import params as pm
    rec = {"comm_method": candidate.comm.value,
           "comm_method2": (candidate.comm2.value
                            if candidate.comm2 is not None else None),
           "opt": int(candidate.opt),
           "send_method": (candidate.send.value
                           if candidate.send is not None else None),
           "streams_chunks": candidate.chunks}
    if candidate.send is None and base_config is not None:
        sm = getattr(base_config, "send_method", None)
        if isinstance(sm, pm.SendMethod) and sm is not pm.SendMethod.SYNC:
            rec["send_method"] = sm.value
            rec["streams_chunks"] = base_config.streams_chunks
    # Overlap-schedule axes (store schema v5): the raced revolving-buffer
    # depth and per-peer sub-block split, or None when the axis was not
    # raced for this candidate — the fold then keeps the caller's knobs,
    # so an unraced axis cannot clobber an explicit choice (same contract
    # as ``wire``).
    depth = getattr(candidate, "depth", None)
    subs = getattr(candidate, "subblocks", None)
    rec["overlap_depth"] = None if depth is None else int(depth)
    rec["overlap_subblocks"] = None if subs is None else int(subs)
    # Wire axis (store schema v3): the raced wire, or the base config's
    # when the axis was not raced (wire=None candidates were timed with
    # the base's wire — the recorded program must be the measured one).
    # An unresolved "auto" (racers normalize it to native before timing)
    # lands on disk as the native it actually ran.
    w = candidate.wire
    if w is None:
        w = getattr(base_config, "wire_dtype", None)
    rec["wire_dtype"] = w if w in _WIRE_CONCRETE else "native"
    # Whether the wire axis was actually raced (race_wire twins) or just
    # inherited from the base: a later wire="auto" must re-race a record
    # whose native wire never competed against the compressed twin. A
    # raced record also carries the error budget the race ran under
    # (``wire_budget``) — a native winner is only a valid hit for budgets
    # at least as tight (see ``_wire_hit_within_budget``).
    rec["wire_raced"] = candidate.wire is not None
    if rec["wire_raced"] and base_config is not None:
        try:
            rec["wire_budget"] = float(base_config.resolved_wire_budget())
        except AttributeError:
            pass
    if np.isfinite(getattr(candidate, "wire_rel_err", float("nan"))):
        rec["wire_rel_err"] = float(f"{candidate.wire_rel_err:.3e}")
    if np.isfinite(candidate.total_ms):
        rec["total_ms"] = round(float(candidate.total_ms), 4)
    return rec


def wire_record(candidate: Any,
                budget: Optional[float] = None) -> Dict[str, Any]:
    """Serialize an ``autotune_wire`` winner for the ``wire`` slot (the
    wire-only race: comm explicit, ``wire_dtype="auto"``). ``budget`` is
    the error budget the race ran under (recorded so a later LOOSER
    budget re-considers a twin this race rejected)."""
    import numpy as np
    rec = {"wire_dtype": candidate.wire or "native"}
    if budget is not None:
        rec["wire_budget"] = float(budget)
    if np.isfinite(getattr(candidate, "wire_rel_err", float("nan"))):
        rec["wire_rel_err"] = float(f"{candidate.wire_rel_err:.3e}")
    if np.isfinite(candidate.total_ms):
        rec["total_ms"] = round(float(candidate.total_ms), 4)
    return rec


def stamp_demotion(store: "WisdomStore", key: str, slot: str, rung: str,
                   reason: str) -> bool:
    """Mark the recorded winner under ``entries[key][slot]`` as DEMOTED
    (resilience fallback: the cell failed at run time — lowering, compile
    or a guard violation). Stamped records read as misses
    (``_comm_hit_fold``/``_wire_hit_fold``), so the store stops
    recommending the failing cell until a fresh race re-records it (a new
    ``record()`` of the slot replaces the stamped dict wholesale,
    clearing the stamp) — OR until the stamp's TTL expires
    (``$DFFT_DEMOTION_TTL_S``, default 24 h; see
    :func:`demotion_active`): a transient failure must not permanently
    demote a cell. A slot with no record gets a bare stamp — it
    already reads as a miss, but the stamp preserves WHY for
    ``dfft-explain``. Best-effort like every wisdom write."""
    rec = store.lookup(key, slot) or {}
    rec.update({
        "demoted": True,
        "demoted_rung": rung,
        "demoted_reason": str(reason)[:300],
        "demoted_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    })
    ok = store.record(key, slot, rec)
    if ok:
        obs.metrics.inc("wisdom.demotion_stamps")
        obs.notice(
            f"wisdom[{slot}]: demotion stamp (rung {rung}) -> {store.path}",
            name="wisdom.demotion", slot=slot, rung=rung,
            store=store.path)
    return ok


DEMOTION_TTL_ENV = "DFFT_DEMOTION_TTL_S"
_DEMOTION_TTL_DEFAULT_S = 86400.0  # 24 h


def _demotion_ttl_s() -> float:
    try:
        return float(os.environ.get(DEMOTION_TTL_ENV,
                                    str(_DEMOTION_TTL_DEFAULT_S)))
    except ValueError:
        return _DEMOTION_TTL_DEFAULT_S


def demotion_active(rec: Optional[Dict[str, Any]]) -> bool:
    """Whether a demotion stamp on ``rec`` is still IN FORCE. Stamps age
    out after ``$DFFT_DEMOTION_TTL_S`` seconds (default 24 h; ``<= 0``
    restores the pre-TTL permanent-stamp behavior): a transient failure —
    a flaky link, a one-off compile hiccup — must not permanently demote
    a cell the store once measured as the winner. An expired stamp reads
    as a hit again (noticed once per read via ``wisdom.demotion_expired``
    so the re-admission is visible in the event log); the stamp itself
    stays on disk until the next ``record()`` replaces it, preserving the
    failure history for ``dfft-explain``. A stamp whose ``demoted_at``
    is missing or unparseable never expires (conservative: the failure
    evidence is real even if its clock is not)."""
    if not rec or not rec.get("demoted"):
        return False
    ttl = _demotion_ttl_s()
    if ttl <= 0:
        return True
    stamped = rec.get("demoted_at")
    if not isinstance(stamped, str):
        return True
    try:
        import calendar
        t = calendar.timegm(time.strptime(stamped, "%Y-%m-%dT%H:%M:%SZ"))
    except ValueError:
        return True
    age = time.time() - t
    if age <= ttl:
        return True
    obs.metrics.inc("wisdom.demotion_expired")
    obs.notice(
        f"wisdom: demotion stamp expired ({age:.0f} s > ttl {ttl:.0f} s, "
        f"rung {rec.get('demoted_rung')}) — record re-admitted",
        name="wisdom.demotion_expired", rung=rec.get("demoted_rung"),
        age_s=round(age, 1), ttl_s=ttl)
    return False


def _valid_local_rec(rec: Dict[str, Any]) -> bool:
    from ..ops.fft import BACKENDS
    if rec.get("fft_backend") not in BACKENDS:
        return False
    prec = rec.get("mxu_precision")
    if prec is not None and str(prec).lower() not in ("default", "high",
                                                      "highest"):
        return False
    dm = rec.get("mxu_direct_max")
    return dm is None or (isinstance(dm, int) and dm >= 1)


def _fold_local_rec(cfg: Any, rec: Dict[str, Any]) -> Any:
    import dataclasses as dc
    return dc.replace(cfg, fft_backend=rec["fft_backend"],
                      mxu_precision=rec.get("mxu_precision"),
                      mxu_direct_max=rec.get("mxu_direct_max"))


def _fold_comm_rec(cfg: Any, rec: Dict[str, Any]) -> Any:
    """Fold a stored comm record into a Config; raises on stale/invalid
    fields (callers treat that as a miss)."""
    import dataclasses as dc

    from .. import params as pm
    comm = pm.CommMethod.parse(rec["comm_method"])
    comm2 = (pm.CommMethod.parse(rec["comm_method2"])
             if rec.get("comm_method2") else None)
    opt = int(rec.get("opt", 0))
    if opt not in (0, 1):
        raise ValueError(f"stale opt {opt}")
    cfg = dc.replace(cfg, comm_method=comm, comm_method2=comm2, opt=opt)
    if rec.get("send_method"):
        chunks = rec.get("streams_chunks")
        if chunks is not None and (not isinstance(chunks, int) or chunks < 1):
            raise ValueError(f"stale streams_chunks {chunks!r}")
        cfg = dc.replace(cfg, send_method=pm.SendMethod.parse(
            rec["send_method"]), send_method2=None, streams_chunks=chunks)
    # Overlap-schedule axes (v5 records): fold only when the axis was
    # raced; a record carrying None keeps the base knobs.
    depth = rec.get("overlap_depth")
    if depth is not None:
        if not isinstance(depth, int) or depth < 2:
            raise ValueError(f"stale overlap_depth {depth!r}")
        cfg = dc.replace(cfg, overlap_depth=depth)
    subs = rec.get("overlap_subblocks")
    if subs is not None:
        if not isinstance(subs, int) or subs < 1:
            raise ValueError(f"stale overlap_subblocks {subs!r}")
        cfg = dc.replace(cfg, overlap_subblocks=subs)
    # v3 records always carry the wire axis; a hand-edited record missing
    # it folds as native (the conservative, bit-identical wire).
    wire = rec.get("wire_dtype", "native")
    if wire not in _WIRE_CONCRETE:
        raise ValueError(f"stale wire_dtype {wire!r}")
    return dc.replace(cfg, wire_dtype=wire)


def _fold_wire_rec(cfg: Any, rec: Dict[str, Any]) -> Any:
    """Fold a stored ``wire``-slot record into a Config; raises on
    stale/invalid fields (callers treat that as a miss)."""
    import dataclasses as dc
    wire = rec.get("wire_dtype")
    if wire not in _WIRE_CONCRETE:
        raise ValueError(f"stale wire_dtype {wire!r}")
    return dc.replace(cfg, wire_dtype=wire)


def _wire_hit_within_budget(rec: Dict[str, Any], budget: float) -> bool:
    """Whether a recorded wire winner satisfies the CALLER'S error budget.
    The budget is not part of the plan key (two runs differing only in
    ``wire_error_budget`` share an entry), so the check happens at fold
    time, in both directions:

    * a recorded bf16 winner hits only if its recorded measured error is
      within the caller's — possibly tighter — budget (missing error
      field = miss, re-race under the caller's budget);
    * a recorded NATIVE winner hits only for budgets at least as tight as
      the one it was raced under (``wire_budget``): a LOOSER caller
      budget could admit the compressed twin that race rejected, so the
      hit must re-race rather than permanently pin native. A legacy
      record without ``wire_budget`` hits (native is always numerically
      safe; only a possible perf win is at stake, and the next raced
      record repairs the field)."""
    if rec.get("wire_dtype") == "bf16":
        err = rec.get("wire_rel_err")
        return isinstance(err, (int, float)) and err <= budget
    raced = rec.get("wire_budget")
    if not isinstance(raced, (int, float)):
        return True
    return budget <= raced


def _no_collectives(kind: str, partition: Any, variant: Any,
                    dims: int) -> bool:
    """Whether a plan configuration issues no exchange at all (single
    rank, the embarrassingly-parallel batched2d batch sharding, or a
    dims<2 partial transform): its comm/wire 'auto' markers resolve to
    defaults without any store consult or race. ONE predicate shared by
    ``_resolve_comm``/``_resolve_wire`` and the lookup-only
    ``peek_config`` so dfft-explain can never disagree with plan
    construction about whether a slot was consulted."""
    single = partition.num_ranks == 1 or (kind == "batched2d"
                                          and variant == "batch")
    return single or dims < 2


def _comm_hit_fold(norm_base: Any, rec: Dict[str, Any], race_wire: bool,
                   budget: float) -> Any:
    """``(folded Config or None, miss-reason or None)`` for a stored
    ``comm`` record — the single hit/miss decision shared by
    ``_resolve_comm`` and the lookup-only ``peek_config`` (dfft-explain),
    so the explain surface can never disagree with what plan construction
    would do."""
    if rec is None:
        return None, "no record"
    if demotion_active(rec):
        # Resilience fallback stamped this cell after a runtime failure
        # (lowering/compile/guard): the store must stop recommending it.
        # A miss re-races and re-records, clearing the stamp; an aged
        # stamp ($DFFT_DEMOTION_TTL_S) expires and reads as a hit again.
        return None, "record demoted after a runtime failure"
    try:
        folded = _fold_comm_rec(norm_base, rec)
    except (KeyError, TypeError, ValueError):
        return None, "stale record"  # re-measure
    if race_wire and not rec.get("wire_raced"):
        # The record predates a wire race the caller delegated (its
        # native wire never competed against the compressed twin): an
        # ordinary miss, re-raced with the wire axis.
        return None, "record predates the wire race"
    if race_wire and not _wire_hit_within_budget(rec, budget):
        # Recorded bf16 winner, but its measured error exceeds THIS
        # caller's (tighter) budget: re-race under it.
        return None, "recorded wire winner fails this error budget"
    if not race_wire and folded.wire_dtype != norm_base.wire_dtype:
        # The record's comm/send/opt winner was raced under a DIFFERENT
        # wire encoding than the caller's explicit one; its ranking may
        # not transfer (compression changes the exchange bytes the race
        # compared), and a fold must reproduce a program the race
        # actually timed. Re-race at the caller's wire — the new record
        # then carries it.
        return None, "record raced under a different wire encoding"
    return folded, None


def _wire_hit_fold(base: Any, rec: Dict[str, Any], budget: float) -> Any:
    """``(folded Config or None, miss-reason or None)`` for a stored
    ``wire``-slot record (shared by ``_resolve_wire`` and
    ``peek_config``)."""
    if rec is None:
        return None, "no record"
    if demotion_active(rec):
        return None, "record demoted after a runtime failure"
    try:
        folded = _fold_wire_rec(base, rec)
    except (KeyError, TypeError, ValueError):
        return None, "stale record"
    if not _wire_hit_within_budget(rec, budget):
        # Budget is not part of the plan key: check at fold time.
        return None, "recorded wire winner fails this error budget"
    return folded, None


def _describe_comm(cfg: Any) -> str:
    """Compact human label of a resolved comm/send/opt/wire choice (the
    provenance notices and dfft-explain share it)."""
    from .. import params as pm
    tag = cfg.comm_method.value
    if cfg.comm_method2 is not None:
        tag += f"+{cfg.comm_method2.value}"
    tag += f"/opt{cfg.opt}"
    if cfg.send_method is pm.SendMethod.RING_OVERLAP:
        tag += "/ring-ovl"
        if cfg.resolved_overlap_depth() != 2:
            tag += f"-d{cfg.resolved_overlap_depth()}"
    elif cfg.send_method is pm.SendMethod.RING:
        tag += "/ring"
    elif cfg.send_method is pm.SendMethod.STREAMS:
        tag += f"/streams{cfg.resolved_streams_chunks()}"
    if cfg.resolved_overlap_subblocks() > 1:
        tag += f"/sub{cfg.resolved_overlap_subblocks()}"
    if cfg.wire_dtype != "native":
        tag += f"/{cfg.wire_dtype}"
    return tag


def _hit_notice(slot: str, detail: str, store: Any) -> None:
    obs.metrics.inc("wisdom.hits")
    src = store.path if store is not None else "no store"
    obs.notice(f"wisdom[{slot}]: hit ({detail}) <- {src}",
               name="wisdom.provenance", slot=slot, status="hit",
               detail=detail, store=getattr(store, "path", None))


def _miss_notice(slot: str, reason: str, store: Any,
                 action: str) -> None:
    obs.metrics.inc("wisdom.misses")
    src = store.path if store is not None else "no store configured"
    obs.notice(f"wisdom[{slot}]: miss ({reason}; {src}) -> {action}",
               name="wisdom.provenance", slot=slot, status="miss",
               reason=reason, store=getattr(store, "path", None))


def resolve_local_backend(shape: Sequence[int], double_prec: bool = False,
                          path: Optional[str] = None, enabled: bool = True,
                          race_on_miss: bool = True,
                          default: str = _FALLBACK_BACKEND,
                          ) -> Tuple[str, Optional[Dict[str, Any]]]:
    """``(backend, record-or-None)`` for a BARE single-device transform of
    ``shape`` (no plan around it — the ``dfft-reference`` testcase-0 path
    and bench.py's warm-start): wisdom hit -> the recorded winner; miss ->
    bounded race-and-record when ``race_on_miss`` (else ``default``); any
    failure degrades to ``default``."""
    store = open_store(path, enabled)
    key = local_key(shape, double_prec)
    rec = store.lookup(key, "local_fft") if store else None
    if rec is not None and _valid_local_rec(rec):
        _hit_notice("local_fft", rec["fft_backend"], store)
        return rec["fft_backend"], rec
    if not race_on_miss:
        return default, None
    _miss_notice("local_fft",
                 "no record" if rec is None else "stale record", store,
                 "racing local-FFT backends")
    from ..testing import autotune as at
    try:
        ranked = at.autotune_local_fft(shape, k=_race_k(),
                                       repeats=_RACE_REPEATS,
                                       inner=_RACE_INNER,
                                       double_prec=double_prec)
    except Exception:  # noqa: BLE001 — wisdom degrades, never errors
        return default, None
    if not ranked or not ranked[0].ok:
        return default, None
    best = ranked[0]
    rec = local_fft_record(best)
    if store:
        store.record(key, "local_fft", rec)
    return best.backend, rec


# ---------------------------------------------------------------------------
# construction-time resolution of Config "auto" fields
# ---------------------------------------------------------------------------

def unresolved(config: Any) -> bool:
    """True when the Config still carries an 'auto' (or an unset local-FFT
    backend) the engines should have resolved at plan construction."""
    from .. import params as pm
    return config.fft_backend is None or pm.AUTO in (
        config.fft_backend, config.comm_method, config.comm_method2,
        config.wire_dtype)


def plan_platform(mesh: Any = None) -> str:
    """The platform a plan's programs run on: the mesh devices' (a
    described topology counts), else JAX's default backend."""
    if mesh is not None:
        return next(iter(mesh.devices.flat)).platform
    import jax
    return jax.default_backend()


def platform_default(config: Any, global_size: Any, mesh: Any = None,
                     dims: int = 3) -> Tuple[Dict[str, Any], str]:
    """``(fields, reason)``: the local-FFT Config fields for a Config that
    names no backend, read from what the plan can observe — the
    platform, the dtype and the transformed extents (the last ``dims``
    slots of ``global_size``; batched2d's batch slot is not one). The
    platform is the mesh devices' (a described topology counts), else
    JAX's default backend.

    On a TPU, float32 with every extent 5-smooth and the largest in
    ``mxu_fft.DIRECT_MAX_BY_EXTENT``: ``"matmul"`` at
    ``mxu_precision="highest"`` (float32 products and accumulation; the
    bf16 passes of DEFAULT/HIGH are not float32 arithmetic) and that
    extent's ``mxu_direct_max``; an explicit precision or threshold is
    kept. Everywhere else ``"xla"``: XLA's FFT on CPU/GPU, no f64 matmul
    on the MXU, a non-smooth axis leaves the matmul fast path
    (``models.base.notice_axis_smoothness``), and past the table XLA's
    FFT measured faster."""
    from ..ops import mxu_fft
    from ..ops.bluestein import is_smooth
    platform = plan_platform(mesh)
    extents = [int(n) for n in tuple(global_size.shape)[-dims:]]
    xla = {"fft_backend": "xla"}
    if platform != "tpu":
        return xla, f"platform default: {platform}"
    if config.double_prec:
        return xla, "platform default: tpu, float64"
    rough = sorted({n for n in extents if not is_smooth(n)})
    if rough:
        return xla, f"platform default: tpu, float32, non-smooth {rough}"
    direct_max = mxu_fft.direct_max_for(max(extents))
    if direct_max is None:
        return xla, (f"platform default: tpu, float32, extent "
                     f"{max(extents)} past the MXU table")
    fields = {"fft_backend": "matmul"}
    if config.mxu_precision is None:
        fields["mxu_precision"] = "highest"
    if config.mxu_direct_max is None:
        fields["mxu_direct_max"] = direct_max
    return fields, "platform default: tpu, float32, smooth"



def _race_shape(kind: str, global_size: Any, partition: Any,
                variant: Optional[str]) -> Tuple[int, ...]:
    """The per-rank block the plan's local transforms actually see — what
    the local-FFT race should time (racing the full global cube on one
    device would both mis-rank and risk OOM at scale)."""
    from .. import params as pm
    shape = list(global_size.shape)
    if isinstance(partition, pm.PencilPartition):
        shape[0] = max(1, pm.padded_extent(shape[0], partition.p1)
                       // partition.p1)
        shape[1] = max(1, pm.padded_extent(shape[1], partition.p2)
                       // partition.p2)
    elif partition.num_ranks > 1:
        # Slab decomposes x (slot 0). Batched2d slots are (batch, nx, ny):
        # shard='batch' decomposes slot 0, shard='x' slot 1.
        ax = 1 if (kind == "batched2d" and variant == "x") else 0
        p = partition.num_ranks
        shape[ax] = max(1, pm.padded_extent(shape[ax], p) // p)
    return tuple(shape)


def _resolve_local_fft(cfg: Any, store: Any, key: str, kind: str,
                       global_size: Any, partition: Any,
                       variant: Any) -> Any:
    import dataclasses as dc

    rec = store.lookup(key, "local_fft") if store else None
    if rec is not None and _valid_local_rec(rec):
        _hit_notice("local_fft", rec["fft_backend"], store)
        return _fold_local_rec(cfg, rec)
    _miss_notice("local_fft",
                 "no record" if rec is None else "stale record", store,
                 "racing local-FFT backends")
    from ..testing import autotune as at
    shape = _race_shape(kind, global_size, partition, variant)
    best = None
    try:
        ranked = at.autotune_local_fft(shape, k=_race_k(),
                                       repeats=_RACE_REPEATS,
                                       inner=_RACE_INNER,
                                       double_prec=cfg.double_prec)
        if ranked and ranked[0].ok:
            best = ranked[0]
    except Exception:  # noqa: BLE001 — wisdom degrades, never errors
        best = None
    if best is None:
        return dc.replace(cfg, fft_backend=_FALLBACK_BACKEND)
    cfg = dc.replace(cfg, fft_backend=best.backend,
                     mxu_precision=best.precision,
                     mxu_direct_max=best.direct_max)
    if store:
        store.record(key, "local_fft", local_fft_record(best))
    return cfg


def _comm_defaults(cfg: Any) -> Any:
    """Clear comm/wire 'auto' markers to the dataclass defaults (used when
    the plan issues no collectives, or when every raced strategy failed —
    the wire default is the bit-identical native, never a silent lossy
    choice)."""
    import dataclasses as dc

    from .. import params as pm
    kw = {}
    if cfg.comm_method == pm.AUTO:
        kw["comm_method"] = pm.CommMethod.ALL2ALL
    if cfg.comm_method2 == pm.AUTO:
        kw["comm_method2"] = None
    if cfg.wire_dtype == pm.AUTO:
        kw["wire_dtype"] = "native"
    return dc.replace(cfg, **kw) if kw else cfg


def _send_encoding() -> Tuple[Any, ...]:
    """The index-based SendMethod wire order shared by the multihost
    broadcast encoders/decoders (``_broadcast_comm_hit``,
    ``_agree_across_processes``) — enum definition order, defined once so
    a new SendMethod cannot be added to one side of the encoding only."""
    from .. import params as pm
    return tuple(pm.SendMethod)


def _broadcast_comm_hit(folded: Any, base: Any) -> Any:
    """Process 0's hit/miss decision, agreed everywhere: a per-host wisdom
    store can hit on some processes and miss on others, and a process that
    skips the race while its peers run collective plan timings deadlocks
    the job. Encodes ``folded`` (a Config, or None for miss) as a
    fixed-width int vector from process 0; every process decodes the same
    answer (None -> all race together)."""
    import numpy as np
    from jax.experimental import multihost_utils

    from .. import params as pm
    comms = (pm.CommMethod.ALL2ALL, pm.CommMethod.PEER2PEER)
    sends = _send_encoding()
    if folded is None:
        vec = np.full(9, -1, dtype=np.int64)
    else:
        vec = np.asarray([
            1,
            comms.index(folded.comm_method),
            (-1 if folded.comm_method2 is None
             else comms.index(folded.comm_method2)),
            int(folded.opt),
            sends.index(folded.send_method),
            (-1 if folded.streams_chunks is None
             else int(folded.streams_chunks)),
            _WIRE_CONCRETE.index(folded.wire_dtype),
            (-1 if folded.overlap_depth == pm.AUTO
             else int(folded.overlap_depth)),
            (-1 if folded.overlap_subblocks is None
             else int(folded.overlap_subblocks)),
        ], dtype=np.int64)
    with obs.span("wisdom.broadcast", what="comm_hit"):
        vec = np.asarray(multihost_utils.broadcast_one_to_all(vec))
    if int(vec[0]) != 1:
        return None
    import dataclasses as dc
    return dc.replace(
        base,
        comm_method=comms[int(vec[1])],
        comm_method2=None if vec[2] < 0 else comms[int(vec[2])],
        opt=int(vec[3]),
        send_method=sends[int(vec[4])], send_method2=None,
        streams_chunks=None if vec[5] < 0 else int(vec[5]),
        wire_dtype=_WIRE_CONCRETE[int(vec[6])],
        overlap_depth=pm.AUTO if vec[7] < 0 else int(vec[7]),
        overlap_subblocks=None if vec[8] < 0 else int(vec[8]))


def _resolve_comm(cfg: Any, store: Any, key: str, kind: str,
                  global_size: Any, partition: Any, mesh: Any,
                  sequence: Any, transform: str, dims: int,
                  variant: Any) -> Any:
    import dataclasses as dc

    import jax

    from .. import params as pm

    if _no_collectives(kind, partition, variant, dims):
        return _comm_defaults(cfg)
    # "auto" owns the whole comm x send x opt x chunks choice (params.py
    # contract): hits fold and winners apply onto a SYNC-normalized base,
    # never onto an explicit send_method the race did not measure. A
    # wire_dtype="auto" riding along normalizes to native here and is
    # raced as the wire axis of the same comm race (race_wire), so one
    # race — and one stored record — owns both choices. The overlap
    # depth/sub-block knobs normalize to defaults the same way (v5: the
    # race owns those axes too — depth and split variants are candidates).
    race_wire = cfg.wire_dtype == pm.AUTO
    norm_base = dc.replace(_comm_defaults(cfg),
                           send_method=pm.SendMethod.SYNC,
                           send_method2=None, streams_chunks=None,
                           overlap_depth=pm.AUTO, overlap_subblocks=None)
    rec = store.lookup(key, "comm") if store else None
    folded, reason = _comm_hit_fold(norm_base, rec, race_wire,
                                    cfg.resolved_wire_budget())
    if jax.process_count() > 1:
        had_local = folded is not None
        folded = _broadcast_comm_hit(folded, norm_base)
        if folded is None and had_local:
            reason = "process 0 missed"
    if folded is not None:
        _hit_notice("comm", _describe_comm(folded), store)
        return folded
    _miss_notice("comm", reason or "no record", store,
                 "racing the comm matrix"
                 + (" (wire axis included)" if race_wire else ""))
    from ..testing import autotune as at
    base = dc.replace(norm_base, comm_method=pm.CommMethod.ALL2ALL,
                      comm_method2=None)
    try:
        ranked = at.autotune_comm(kind, global_size, partition, base,
                                  mesh=mesh, sequence=sequence,
                                  iterations=_COMM_ITERATIONS,
                                  warmup=_COMM_WARMUP, dims=dims,
                                  transform=transform, race_send=True,
                                  race_wire=race_wire)
        cfg = at.apply_best_comm(ranked, norm_base)
    except Exception:  # noqa: BLE001 — degrade to defaults, never error
        return _comm_defaults(cfg)
    if store:
        store.record(key, "comm", comm_record(ranked[0], base))
    return cfg


def _broadcast_wire_hit(folded: Any, base: Any) -> Any:
    """Process 0's wire hit/miss decision, agreed everywhere (the wire
    race times collective plans, so a per-host hit/miss split deadlocks —
    same contract as ``_broadcast_comm_hit``)."""
    import numpy as np
    from jax.experimental import multihost_utils
    code = (-1 if folded is None
            else _WIRE_CONCRETE.index(folded.wire_dtype))
    with obs.span("wisdom.broadcast", what="wire_hit"):
        code = int(multihost_utils.broadcast_one_to_all(np.int64(code)))
    if code < 0:
        return None
    import dataclasses as dc
    return dc.replace(base, wire_dtype=_WIRE_CONCRETE[code])


def _resolve_wire(cfg: Any, store: Any, key: str, kind: str,
                  global_size: Any, partition: Any, mesh: Any,
                  sequence: Any, transform: str, dims: int,
                  variant: Any) -> Any:
    """Resolve ``wire_dtype="auto"`` when the comm choice is EXPLICIT
    (comm "auto" resolves both axes in one race — ``_resolve_comm``):
    wisdom ``wire``-slot hit -> reuse; miss -> race native vs bf16 on the
    caller's fixed rendering under the error budget
    (``autotune_wire``) and record; plans without an exchange -> native."""
    import dataclasses as dc

    import jax

    from .. import params as pm

    if _no_collectives(kind, partition, variant, dims):
        return dc.replace(cfg, wire_dtype="native")
    base = dc.replace(cfg, wire_dtype="native")
    rec = store.lookup(key, "wire") if store else None
    folded, reason = _wire_hit_fold(base, rec, cfg.resolved_wire_budget())
    if jax.process_count() > 1:
        had_local = folded is not None
        folded = _broadcast_wire_hit(folded, base)
        if folded is None and had_local:
            reason = "process 0 missed"
    if folded is not None:
        _hit_notice("wire", folded.wire_dtype, store)
        return folded
    _miss_notice("wire", reason or "no record", store,
                 "racing native vs bf16 on the fixed rendering")
    from ..testing import autotune as at
    try:
        ranked = at.autotune_wire(kind, global_size, partition, base,
                                  mesh=mesh, sequence=sequence,
                                  iterations=_COMM_ITERATIONS,
                                  warmup=_COMM_WARMUP, dims=dims,
                                  transform=transform)
        best = ranked[0]
        if not best.ok:
            return base
        # Fold ONLY the wire axis (apply_best_comm would also fold the
        # candidate's mirrored comm/send fields, clobbering an explicit
        # send_method2 the wire-only race never measured differently).
        cfg = dc.replace(base, wire_dtype=best.wire or "native")
    except Exception:  # noqa: BLE001 — degrade to native, never error
        return base
    if store:
        store.record(key, "wire",
                     wire_record(best, base.resolved_wire_budget()))
    return cfg


def _agree_across_processes(cfg: Any) -> Any:
    """Multi-controller runs must agree on the resolved Config: measured
    winners are routinely within noise across processes, and divergent
    Configs build mismatched collective programs (hang). Broadcast process
    0's resolution as a fixed-width int vector (the same contract as
    ``autotune_comm``'s winner broadcast)."""
    import jax
    if jax.process_count() <= 1:
        return cfg
    import dataclasses as dc

    import numpy as np
    from jax.experimental import multihost_utils

    from .. import params as pm
    from ..ops.fft import BACKENDS
    precs = (None, "default", "high", "highest")
    comms = (pm.CommMethod.ALL2ALL, pm.CommMethod.PEER2PEER)
    sends = _send_encoding()
    vec = np.asarray([
        BACKENDS.index(cfg.fft_backend),
        precs.index(cfg.mxu_precision if cfg.mxu_precision is None
                    else str(cfg.mxu_precision).lower()),
        -1 if cfg.mxu_direct_max is None else int(cfg.mxu_direct_max),
        comms.index(cfg.comm_method),
        -1 if cfg.comm_method2 is None else comms.index(cfg.comm_method2),
        int(cfg.opt),
        sends.index(cfg.send_method),
        -1 if cfg.streams_chunks is None else int(cfg.streams_chunks),
        _WIRE_CONCRETE.index(cfg.wire_dtype),
        -1 if cfg.overlap_depth == pm.AUTO else int(cfg.overlap_depth),
        (-1 if cfg.overlap_subblocks is None
         else int(cfg.overlap_subblocks)),
    ], dtype=np.int64)
    with obs.span("wisdom.broadcast", what="resolved_config"):
        vec = np.asarray(multihost_utils.broadcast_one_to_all(vec))
    return dc.replace(
        cfg,
        fft_backend=BACKENDS[int(vec[0])],
        mxu_precision=precs[int(vec[1])],
        mxu_direct_max=None if vec[2] < 0 else int(vec[2]),
        comm_method=comms[int(vec[3])],
        comm_method2=None if vec[4] < 0 else comms[int(vec[4])],
        opt=int(vec[5]),
        send_method=sends[int(vec[6])],
        streams_chunks=None if vec[7] < 0 else int(vec[7]),
        wire_dtype=_WIRE_CONCRETE[int(vec[8])],
        overlap_depth=pm.AUTO if vec[9] < 0 else int(vec[9]),
        overlap_subblocks=None if vec[10] < 0 else int(vec[10]))


def resolve_config(kind: str, global_size: Any, partition: Any,
                   config: Any = None, *, mesh: Any = None,
                   sequence: Any = None, transform: str = "r2c",
                   dims: int = 3, variant: Optional[str] = None) -> Any:
    """Resolve a Config's ``fft_backend="auto"`` / ``comm_method="auto"``
    / ``wire_dtype="auto"`` markers into measured concrete values: wisdom
    hit -> reuse silently; miss -> bounded race (accuracy-gated by the
    underlying autotuners; the wire race additionally by
    ``wire_error_budget``) and record; no usable store -> race without
    recording. An unset ``fft_backend`` takes ``platform_default``,
    counted as ``plan.backend_default[backend=,direct_max=,precision=]``.
    Configs without an 'auto' marker pass through untouched otherwise —
    the zero-cost common case every plan constructor calls. A wire "auto"
    rides the comm race (one record) when comm is "auto" too, and runs
    the dedicated wire-only race (``wire`` slot) when comm is explicit."""
    from .. import params as pm
    cfg = config if config is not None else pm.Config()
    if cfg.fft_backend is None:
        import dataclasses as dc
        fields, _ = platform_default(cfg, global_size, mesh, dims)
        cfg = dc.replace(cfg, **fields)
        obs.metrics.inc(obs.metrics.labeled(
            "plan.backend_default", backend=cfg.fft_backend,
            precision=cfg.mxu_precision, direct_max=cfg.mxu_direct_max))
    wants_fft = cfg.fft_backend == pm.AUTO
    wants_comm = pm.AUTO in (cfg.comm_method, cfg.comm_method2)
    wants_wire = cfg.wire_dtype == pm.AUTO
    if not (wants_fft or wants_comm or wants_wire):
        return cfg
    with obs.span("plan.resolve", kind=kind,
                  shape=list(global_size.shape), transform=transform,
                  dims=dims):
        store = store_for_config(cfg)
        key = plan_key(kind, global_size.shape, cfg.double_prec, partition,
                       cfg.norm, transform=transform, sequence=sequence,
                       variant=variant,
                       mesh_shape=_mesh_shape_of(mesh, partition), dims=dims)
        if wants_fft:
            cfg = _resolve_local_fft(cfg, store, key, kind, global_size,
                                     partition, variant)
        if wants_comm:
            # Owns the wire axis too when it is "auto" (race_wire).
            cfg = _resolve_comm(cfg, store, key, kind, global_size,
                                partition, mesh, sequence, transform, dims,
                                variant)
        elif wants_wire:
            cfg = _resolve_wire(cfg, store, key, kind, global_size,
                                partition, mesh, sequence, transform, dims,
                                variant)
        return _agree_across_processes(cfg)


def peek_config(kind: str, global_size: Any, partition: Any,
                config: Any = None, *, mesh: Any = None,
                sequence: Any = None, transform: str = "r2c",
                dims: int = 3,
                variant: Optional[str] = None) -> Tuple[Any, Dict[str, Any]]:
    """LOOKUP-ONLY resolution + provenance: ``(cfg, provenance)``.

    The ``dfft-explain`` surface — it must report the fully resolved plan
    WITHOUT executing anything, so unlike ``resolve_config`` a miss never
    races: it folds the same defaults a raceless resolution would
    (``fft_backend`` -> the xla fallback, comm/wire -> ``_comm_defaults``)
    and reports the slot as a miss. Hit/miss decisions go through the
    exact helpers ``_resolve_comm``/``_resolve_wire`` use
    (``_comm_hit_fold``/``_wire_hit_fold``), so explain can never disagree
    with what plan construction would do on the same store.

    ``provenance`` = ``{"store_path", "store_version" (on-disk, pre-
    migration, None when absent), "key", "slots": {slot: {"status":
    "hit"|"miss"|"not consulted (...)", "reason", "record"}},
    "backend_default": the ``platform_default`` reason, or None when the
    Config named its backend}``. Slots appear only for Config fields that
    were actually ``"auto"``."""
    import dataclasses as dc

    from .. import params as pm
    cfg = config if config is not None else pm.Config()
    default_reason = None
    if cfg.fft_backend is None:
        fields, default_reason = platform_default(cfg, global_size, mesh,
                                                  dims)
        cfg = dc.replace(cfg, **fields)
    store = store_for_config(cfg)
    key = plan_key(kind, global_size.shape, cfg.double_prec, partition,
                   cfg.norm, transform=transform, sequence=sequence,
                   variant=variant,
                   mesh_shape=_mesh_shape_of(mesh, partition), dims=dims)
    prov = {"store_path": store.path if store else None,
            "store_version": store.raw_version() if store else None,
            "key": key, "slots": {}, "backend_default": default_reason}
    wants_fft = cfg.fft_backend == pm.AUTO
    wants_comm = pm.AUTO in (cfg.comm_method, cfg.comm_method2)
    wants_wire = cfg.wire_dtype == pm.AUTO
    no_coll = _no_collectives(kind, partition, variant, dims)
    if wants_fft:
        rec = store.lookup(key, "local_fft") if store else None
        if rec is not None and _valid_local_rec(rec):
            cfg = _fold_local_rec(cfg, rec)
            prov["slots"]["local_fft"] = {"status": "hit", "record": rec}
        else:
            cfg = dc.replace(cfg, fft_backend=_FALLBACK_BACKEND)
            prov["slots"]["local_fft"] = {
                "status": "miss",
                "reason": "no record" if rec is None else "stale record"}
    if wants_comm:
        if no_coll:
            cfg = _comm_defaults(cfg)
            prov["slots"]["comm"] = {
                "status": "not consulted (plan issues no collectives)"}
        else:
            race_wire = cfg.wire_dtype == pm.AUTO
            norm_base = dc.replace(_comm_defaults(cfg),
                                   send_method=pm.SendMethod.SYNC,
                                   send_method2=None, streams_chunks=None)
            rec = store.lookup(key, "comm") if store else None
            folded, reason = _comm_hit_fold(norm_base, rec, race_wire,
                                            cfg.resolved_wire_budget())
            if folded is not None:
                cfg = folded
                prov["slots"]["comm"] = {"status": "hit", "record": rec}
            else:
                cfg = norm_base
                prov["slots"]["comm"] = {"status": "miss", "reason": reason,
                                         "record": rec}
    elif wants_wire:
        if no_coll:
            cfg = dc.replace(cfg, wire_dtype="native")
            prov["slots"]["wire"] = {
                "status": "not consulted (plan issues no collectives)"}
        else:
            base = dc.replace(cfg, wire_dtype="native")
            rec = store.lookup(key, "wire") if store else None
            folded, reason = _wire_hit_fold(base, rec,
                                            cfg.resolved_wire_budget())
            if folded is not None:
                cfg = folded
                prov["slots"]["wire"] = {"status": "hit", "record": rec}
            else:
                cfg = base
                prov["slots"]["wire"] = {"status": "miss", "reason": reason,
                                         "record": rec}
    return cfg, prov

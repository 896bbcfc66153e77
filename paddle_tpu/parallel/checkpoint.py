"""Async, reshardable, crash-consistent training checkpoints.

Reference analog: save/load ops streamed per var (save_op.cc, load_op.cc;
io.py:487 save_persistables) plus the pserver checkpoint-notify hook
(distributed_ops/checkpoint_notify_op.cc). The reference cannot restore
under a different device topology (SURVEY §5 "no optimizer-state resharding
on topology change"), and a torn or bit-rotted checkpoint file kills the
restore outright; this module fixes both — the TPU-native bar.

Design (orbax-style, self-contained):
- `save` snapshots every persistable var to host (device→host copies are
  started async, then a background thread finishes materialization and
  writes the bundle) — the training loop resumes while the write is in
  flight;
- files are written to a temp name, fsynced, and renamed; a per-file
  SHA-256 **manifest** (``ckpt-<step>.manifest-<rank>.json``) is written
  last as the commit record — a preemption mid-write never corrupts the
  previous checkpoint, and a file torn *after* its rename (power loss,
  bitrot) is caught at restore;
- `restore` verifies the manifest before loading anything; on any
  corruption or partial write it walks back newest→older to the most
  recent checkpoint that verifies (``checkpoint/fallback_steps`` counter,
  warning naming the bad files) instead of raising and dying — a run
  resumes from the last GOOD checkpoint, never from a torn one;
- the background writer retries transient I/O errors with capped
  exponential backoff (``PDTPU_CKPT_RETRIES`` attempts,
  ``PDTPU_CKPT_RETRY_BACKOFF_MS`` base delay) before `wait()` surfaces
  the failure with the step and path;
- bundles store plain host arrays, so `restore` works under ANY mesh: the
  compiler lifts host values into whatever sharding the new topology
  declares (CompiledProgram._state_in), which is what makes checkpoints
  reshardable across dp/tp splits.

Crash-consistency is testable, not aspirational: ``paddle_tpu.faults``
probes (`ckpt.bundle_write`, `ckpt.rename`, `ckpt.shard_write`,
`ckpt.marker`) sit at every commit edge, and tests/test_elastic.py's
chaos matrix kills the writer at each of them.
"""
from __future__ import annotations

import hashlib
import json
import os
import pickle
import threading
import time
import warnings
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..core.executor import _RNG_STATE
from ..core.program import Program, default_main_program
from ..core.scope import Scope, _scope
from ..faults import fault_point
from ..observability.registry import get_registry

_OBS = get_registry()
# restore skipped a bad checkpoint and fell back to an older one
_FALLBACK = _OBS.counter("checkpoint/fallback_steps")
# background writer retried a transient I/O failure
_RETRIES = _OBS.counter("checkpoint/write_retries")


def _is_replicated(v) -> bool:
    """Fully-replicated (or single-device) arrays go in the main bundle;
    anything actually sharded takes the per-shard path."""
    try:
        shards = v.addressable_shards
    except Exception:
        return True
    full = tuple(slice(None) for _ in v.shape)
    return all(tuple(s.index) == full for s in shards)


def _zero_state_var(var) -> bool:
    """ZeRO-shardable state (ShardingStrategy): optimizer accumulators,
    master weights, persistent gradient buffers — tagged at creation — and,
    under stage3 (full-parameter FSDP), the trainable parameters themselves.
    TP parameters (explicit `shard_spec`) are excluded: their layout is a
    deliberate model-parallel split, not a ZeRO annotation, so they keep the
    per-shard save path."""
    if var is None:
        return False
    if (getattr(var, "is_optimizer_state", False)
            or getattr(var, "is_master_weight", False)
            or getattr(var, "is_grad_buffer", False)):
        return True
    return bool(getattr(var, "trainable", False)
                and getattr(var, "persistable", False)
                and getattr(var, "shard_spec", None) is None)


def _snapshot(program: Program, scope: Scope):
    """(replicated_vals, shard_records): shard_records holds
    (var, index, device_buffer) triples for THIS process's addressable,
    replica-0 shards only — a sharded parameter is never all-gathered to
    host on the save path (VERDICT r2 #7; at pod scale the gather would
    materialize every parameter fully on every host).

    Exception: ZeRO-sharded optimizer state that is fully addressable and
    small (≤ PDTPU_CKPT_GATHER_MAX_BYTES, default 64 MiB) is gathered into
    the main bundle — the save gathers, the load re-shards, and the
    checkpoint stays a plain layout-independent bundle with no shard-file
    proliferation for every accumulator of every parameter."""
    import jax
    import jax.numpy as jnp

    gather_max = int(os.environ.get("PDTPU_CKPT_GATHER_MAX_BYTES",
                                    str(64 << 20)))
    pvars = {v.name: v for v in program.list_vars() if v.persistable}
    names = list(pvars)
    out = {}
    shard_records = []
    for n in names:
        v = scope.find_var(n)
        if v is None:
            continue
        if isinstance(v, jax.Array):
            if not _is_replicated(v):
                if (_zero_state_var(pvars.get(n))
                        and v.is_fully_addressable
                        and v.nbytes <= gather_max):
                    arr = np.asarray(v)  # host gather, layout erased
                    shp = tuple(pvars[n].shape or ())
                    if (shp and arr.shape != shp and len(arr.shape) == len(shp)
                            and all(a >= b for a, b in zip(arr.shape, shp))):
                        # ZeRO padding fallback stores the leaf padded to a
                        # dp multiple — persist the declared (logical) shape
                        arr = arr[tuple(slice(0, d) for d in shp)]
                    out[n] = arr
                    continue
                for s in v.addressable_shards:
                    if s.replica_id == 0:  # one copy of each distinct piece
                        # own copy: the next training step DONATES the live
                        # shard buffer while the background thread writes
                        d = jnp.copy(s.data)
                        if hasattr(d, "copy_to_host_async"):
                            try:
                                d.copy_to_host_async()
                            except Exception:
                                pass
                        shard_records.append(
                            (n, tuple((sl.start, sl.stop)
                                      for sl in _norm_index(s.index, v.shape)),
                             tuple(v.shape), str(v.dtype), d))
                continue
            # device-side copy: the training loop's next step DONATES the
            # live buffers, so the background writer must own its own copy;
            # then start the d2h transfer without blocking
            v = jnp.copy(v)
            if hasattr(v, "copy_to_host_async"):
                try:
                    v.copy_to_host_async()
                except Exception:
                    pass
        out[n] = v
    return out, shard_records


def _norm_index(index, shape):
    """Normalize a shard index (tuple of slices, possibly with None
    start/stop) to concrete [start, stop) per dim."""
    out = []
    for sl, dim in zip(index, shape):
        out.append(slice(sl.start or 0,
                         dim if sl.stop is None else sl.stop))
    return out


def _write_bytes(path: str, blob: bytes) -> Tuple[str, int]:
    """Write + fsync `blob` to `path`; returns (sha256 hex, size). The
    fsync keeps the manifest honest: once the hash is recorded the bytes
    it covers are durable, so a post-rename power loss can't produce a
    file that passes size checks but reads back zeros."""
    with open(path, "wb") as f:
        f.write(blob)
        f.flush()
        os.fsync(f.fileno())
    return hashlib.sha256(blob).hexdigest(), len(blob)


def _hash_file(path: str) -> Tuple[str, int]:
    h = hashlib.sha256()
    size = 0
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            h.update(chunk)
            size += len(chunk)
    return h.hexdigest(), size


class Checkpointer:
    """`Checkpointer(dirname).save(step)` / `.restore()` over a Program's
    persistables. One background writer thread; `wait()` joins it.

    After a successful `restore()`, ``last_extra`` holds any ``@dataio@*``
    keys the checkpoint carried (the input-pipeline cursor `run_elastic`
    snapshots via ``save(extra=...)``)."""

    def __init__(self, dirname: str, keep: int = 3):
        self.dirname = dirname
        self.keep = keep
        self._thread: Optional[threading.Thread] = None
        # (exception, step, path, attempts) of a failed background write
        self._error: Optional[tuple] = None
        self._current_path: Optional[str] = None
        self.last_extra: Dict[str, object] = {}
        # incremental-checkpoint chain head: the committed full save (or
        # restore) deltas extend — {"step": int, "marks": {table: mark}}.
        # Advanced only by on-commit callbacks / restore, so an aborted
        # write never becomes a delta base.
        self._ps_base: Optional[Dict[str, object]] = None
        os.makedirs(dirname, exist_ok=True)

    def _path(self, step: int) -> str:
        # native bundle when the C++ writer is available, else pickle
        from ..native import available as _native_available
        ext = "ptck" if _native_available() else "pkl"
        return os.path.join(self.dirname, f"ckpt-{step}.{ext}")

    def _existing_path(self, step: int) -> Optional[str]:
        for ext in ("ptck", "pkl"):
            p = os.path.join(self.dirname, f"ckpt-{step}.{ext}")
            if os.path.exists(p):
                return p
        return None

    def _manifest_path(self, step: int, rank) -> str:
        return os.path.join(self.dirname, f"ckpt-{step}.manifest-{rank}.json")

    # -- background write --------------------------------------------------
    def _write(self, step: int, vals: Dict[str, object], shards=(),
               rank: int = 0, on_commit=()):
        """Writer-thread entry: retry transient I/O with capped exponential
        backoff; any residual failure is surfaced by the next wait()/save()
        (a silently lost checkpoint must not look durable). `on_commit`
        callbacks run only after the write fully commits (manifest +
        marker durable) — e.g. PS journal truncation, which must never
        happen for a checkpoint that might not be restorable."""
        retries = int(os.environ.get("PDTPU_CKPT_RETRIES", "3"))
        backoff_ms = float(os.environ.get("PDTPU_CKPT_RETRY_BACKOFF_MS",
                                          "100"))
        attempt = 0
        while True:
            try:
                self._write_impl(step, vals, shards, rank)
                for cb in on_commit:
                    try:
                        cb()
                    except Exception:
                        pass  # commit stands; truncation is best-effort
                return
            except OSError as e:
                # transient filesystem error (NFS blip, EIO, injected
                # fault): every tmp-write/rename in _write_impl is
                # idempotent, so the whole write can simply run again
                path = getattr(e, "filename", None) or self._current_path
                if attempt >= retries:
                    self._error = (e, step, path, attempt)
                    return
                _RETRIES.inc()
                time.sleep(min(backoff_ms * (2 ** attempt), 5000.0) / 1e3)
                attempt += 1
            except BaseException as e:
                self._error = (e, step, self._current_path, attempt)
                return

    def _write_shards(self, step: int, shards, rank: int,
                      manifest: Dict[str, dict]):
        """Per-process shard file + JSON index, both fsync+rename-durable
        and recorded in `manifest`. Each process writes ONLY its
        addressable replica-0 shards; restore merges every rank's index
        (shared-filesystem contract, same as the reference's save_combine
        to a common dirname)."""
        data = {}
        index: Dict[str, dict] = {}
        for name, bounds, shape, dtype, buf in shards:
            key = f"{name}@" + ",".join(f"{a}:{b}" for a, b in bounds)
            data[key] = np.asarray(buf)
            ent = index.setdefault(name, {"shape": list(shape),
                                          "dtype": dtype, "shards": []})
            ent["shards"].append({"key": key,
                                  "bounds": [list(b) for b in bounds]})
        spath = os.path.join(self.dirname, f"ckpt-{step}.shards-{rank}.pkl")
        self._current_path = spath
        digest, size = _write_bytes(spath + ".tmp",
                                    pickle.dumps(data, protocol=4))
        manifest[os.path.basename(spath)] = {"sha256": digest, "bytes": size}
        fault_point("ckpt.shard_write", path=spath + ".tmp")
        os.replace(spath + ".tmp", spath)
        ipath = os.path.join(self.dirname, f"ckpt-{step}.index-{rank}.json")
        self._current_path = ipath
        digest, size = _write_bytes(ipath + ".tmp",
                                    json.dumps(index).encode("utf-8"))
        manifest[os.path.basename(ipath)] = {"sha256": digest, "bytes": size}
        os.replace(ipath + ".tmp", ipath)

    def _write_manifest(self, step: int, rank, manifest: Dict[str, dict]):
        """The commit record: written LAST, after every file it hashes is
        durable under its final name. A step without its manifests is an
        uncommitted (or pre-manifest legacy) checkpoint."""
        mpath = self._manifest_path(step, rank)
        self._current_path = mpath
        blob = json.dumps({"step": step, "rank": rank, "files": manifest},
                          sort_keys=True).encode("utf-8")
        _write_bytes(mpath + ".tmp", blob)
        os.replace(mpath + ".tmp", mpath)

    def _write_impl(self, step: int, vals: Dict[str, object], shards=(),
                    rank: int = 0):
        manifest: Dict[str, dict] = {}
        if shards:
            self._write_shards(step, shards, rank, manifest)
        if rank != 0:
            if manifest:  # this rank's commit record for its shard files
                self._write_manifest(step, rank, manifest)
            return  # replicated vars + marker are rank 0's job
        bundle = {n: np.asarray(v) for n, v in vals.items()}
        path = self._path(step)
        tmp = path + ".tmp"
        self._current_path = path
        if path.endswith(".ptck"):
            # native framed writer (src/ckptio.cc — save_combine_op.cc
            # analog): buffered stdio + fsync off the Python thread
            from ..native import write_bundle
            nb = dict(bundle)
            nb["@step@"] = np.asarray(step, np.int64)
            if write_bundle(tmp, nb):
                digest, size = _hash_file(tmp)
            else:
                # honor write_bundle's documented contract: fall back to
                # pickle rather than losing the checkpoint
                try:
                    os.remove(tmp)
                except OSError:
                    pass
                path = os.path.join(self.dirname, f"ckpt-{step}.pkl")
                tmp = path + ".tmp"
                self._current_path = path
                digest, size = _write_bytes(
                    tmp, pickle.dumps({"step": step, "vars": bundle},
                                      protocol=4))
        else:
            digest, size = _write_bytes(
                tmp, pickle.dumps({"step": step, "vars": bundle},
                                  protocol=4))
        manifest[os.path.basename(path)] = {"sha256": digest, "bytes": size}
        fault_point("ckpt.bundle_write", path=tmp)
        os.replace(tmp, path)  # atomic: never a half-written ckpt-N
        fault_point("ckpt.rename", path=path)
        self._write_manifest(step, 0, manifest)
        marker = os.path.join(self.dirname, "latest")
        self._current_path = marker
        _write_bytes(marker + ".tmp", str(step).encode("ascii"))
        fault_point("ckpt.marker", path=marker + ".tmp")
        os.replace(marker + ".tmp", marker)
        self._gc(step)

    def _gc(self, newest: int):
        steps = sorted(self.all_steps())
        for s in steps[:-self.keep] if self.keep else []:
            if s != newest:
                p = self._existing_path(s)
                if p:
                    try:
                        os.remove(p)
                    except OSError:
                        pass
                for f in os.listdir(self.dirname):
                    if (f.startswith(f"ckpt-{s}.shards-")
                            or f.startswith(f"ckpt-{s}.index-")
                            or f.startswith(f"ckpt-{s}.manifest-")
                            # a delta chain is anchored to its base full
                            # save: once the base is gone the chain can
                            # never replay
                            or f.startswith(f"delta-{s}-")):
                        try:
                            os.remove(os.path.join(self.dirname, f))
                        except OSError:
                            pass

    def all_steps(self):
        out = []
        for f in os.listdir(self.dirname):
            if f.startswith("ckpt-") and (f.endswith(".pkl")
                                          or f.endswith(".ptck")):
                try:
                    out.append(int(f[5:].rsplit(".", 1)[0]))
                except ValueError:
                    pass
        return out

    def latest_step(self) -> Optional[int]:
        marker = os.path.join(self.dirname, "latest")
        if os.path.exists(marker):
            s = None
            try:
                with open(marker) as f:
                    s = int(f.read().strip())
            except (ValueError, OSError):
                # empty or torn marker (crash between open and the rename,
                # or a pre-fsync power loss): fall back to the dir scan
                pass
            if s is not None and self._existing_path(s):
                return s
        steps = self.all_steps()
        return max(steps) if steps else None

    # -- integrity ---------------------------------------------------------
    def verify(self, step: int) -> List[str]:
        """Check every file the step's manifests list (existence, size,
        SHA-256). Returns [] when the step verifies. A step with no
        manifest at all (pre-manifest legacy writer, or a crash after the
        bundle rename but before the commit record) has nothing to check
        against and is trusted as-is — its bundle rename was atomic."""
        problems: List[str] = []
        prefix = f"ckpt-{step}.manifest-"
        for fname in sorted(os.listdir(self.dirname)):
            if not (fname.startswith(prefix) and fname.endswith(".json")):
                continue
            try:
                with open(os.path.join(self.dirname, fname)) as f:
                    listed = json.load(f)["files"]
            except (OSError, ValueError, KeyError) as e:
                problems.append(f"{fname}: unreadable manifest "
                                f"({type(e).__name__}: {e})")
                continue
            for base, ent in sorted(listed.items()):
                p = os.path.join(self.dirname, base)
                try:
                    size = os.path.getsize(p)
                except OSError:
                    problems.append(
                        f"{base}: listed in manifest {fname} but missing")
                    continue
                if int(ent.get("bytes", -1)) != size:
                    problems.append(
                        f"{base}: size {size} != manifest's "
                        f"{ent.get('bytes')} (torn write)")
                    continue
                digest, _ = _hash_file(p)
                if digest != ent.get("sha256"):
                    problems.append(
                        f"{base}: sha256 mismatch vs manifest {fname} "
                        "(corrupt)")
        return problems

    def verified_steps(self) -> List[int]:
        """Every step whose manifest verification passes, newest first —
        the set a serving ModelRegistry may claim lineage from (a torn or
        corrupt training checkpoint never becomes a serving version)."""
        return [s for s in sorted(self.all_steps(), reverse=True)
                if not self.verify(s)]

    # -- incremental (delta) checkpoints ------------------------------------
    def _delta_path(self, base: int, dstep: int) -> str:
        return os.path.join(self.dirname, f"delta-{base}-{dstep}.pkl")

    def _delta_manifest_path(self, base: int, dstep: int) -> str:
        return os.path.join(self.dirname,
                            f"delta-{base}-{dstep}.manifest.json")

    def delta_steps(self, base: int) -> List[int]:
        """Delta steps on disk anchored to full checkpoint `base`,
        ascending (the chain replay order)."""
        out = []
        prefix = f"delta-{base}-"
        for f in os.listdir(self.dirname):
            if f.startswith(prefix) and f.endswith(".pkl"):
                try:
                    out.append(int(f[len(prefix):-len(".pkl")]))
                except ValueError:
                    pass
        return sorted(out)

    def verify_delta(self, base: int, dstep: int) -> List[str]:
        """Manifest check (existence, size, SHA-256) for one delta file;
        [] when it verifies. A delta with no manifest is uncommitted."""
        problems: List[str] = []
        mpath = self._delta_manifest_path(base, dstep)
        try:
            with open(mpath) as f:
                listed = json.load(f)["files"]
        except (OSError, ValueError, KeyError) as e:
            return [f"{os.path.basename(mpath)}: unreadable manifest "
                    f"({type(e).__name__}: {e})"]
        for bname, ent in sorted(listed.items()):
            p = os.path.join(self.dirname, bname)
            try:
                size = os.path.getsize(p)
            except OSError:
                problems.append(f"{bname}: listed in manifest but missing")
                continue
            if int(ent.get("bytes", -1)) != size:
                problems.append(f"{bname}: size {size} != manifest's "
                                f"{ent.get('bytes')} (torn write)")
                continue
            digest, _ = _hash_file(p)
            if digest != ent.get("sha256"):
                problems.append(f"{bname}: sha256 mismatch (corrupt)")
        return problems

    def _delta_chain(self, base: int) -> List[dict]:
        """The longest verifiable prefix of `base`'s delta chain, as
        loaded payload dicts in ascending delta-step order. The walk
        stops at the first unverifiable/unreadable file: every delta
        after a hole is built over state the restore cannot reconstruct,
        so applying it would be silently lossy."""
        chain: List[dict] = []
        for ds in self.delta_steps(base):
            bad = self.verify_delta(base, ds)
            payload = None
            if not bad:
                try:
                    with open(self._delta_path(base, ds), "rb") as f:
                        payload = pickle.load(f)
                except (OSError, EOFError, ValueError,
                        pickle.UnpicklingError) as e:
                    bad = [f"{type(e).__name__}: {e}"]
            if bad:
                warnings.warn(
                    f"delta checkpoint {base}->{ds} in {self.dirname!r} "
                    f"failed verification ({'; '.join(bad)}); stopping the "
                    "delta replay chain here", RuntimeWarning)
                _FALLBACK.inc()
                break
            chain.append(payload)
        return chain

    @staticmethod
    def _apply_delta_chain(chain: List[dict], tname: str,
                           rows: np.ndarray, mark: int):
        """Replay one table's entries from an already-verified chain onto
        the dense `rows` array, in delta order then seq order (scatter-SET
        of absolute rows ⇒ ordered replay is bitwise-exact). Stops at a
        mark discontinuity (a delta whose ``since_mark`` doesn't extend
        the state we hold). Returns (rows, final_mark, deltas_applied)."""
        applied = 0
        for payload in chain:
            blob = (payload.get("tables") or {}).get(tname)
            if blob is None:
                continue
            if int(blob["since_mark"]) != int(mark):
                break
            off = 0
            ids = np.asarray(blob["ids"], np.int64)
            drows = np.asarray(blob["rows"], np.uint16)
            for c in np.asarray(blob["counts"], np.int64).tolist():
                rows[ids[off:off + c]] = drows[off:off + c]
                off += c
            mark = int(blob["mark"])
            applied += 1
        return rows, mark, applied

    def save_delta(self, step: int, ps_tables: Dict[str, object],
                   extra: Optional[Dict[str, object]] = None,
                   blocking: bool = False) -> None:
        """Incremental PS checkpoint: persist only the rows touched since
        the chain head — the journal entries past the last full save's
        (or previous delta's) mark — as ``delta-<base>-<step>.pkl`` plus
        a SHA-256 manifest, committed tmp→fsync→rename like everything
        else. Orders of magnitude smaller than a full dump for a big
        table, so it can run every few seconds on an online trainer.

        Riding the PR 10 journal machinery: each table's flush hook runs
        first (device-dirty rows + queued async pushes land in the
        journal), the snapshot is the journal slice ``(since_mark,
        mark]``, and the journal is truncated to `mark` once — and only
        once — the delta COMMITS, which is what keeps journal memory
        bounded by delta cadence on an unbounded stream.

        Requires a committed full ``save(ps_tables=...)`` (or a
        ``restore``) as the chain base; ``save()`` is the compaction
        point — it rewrites the whole table and starts a fresh chain.
        Restore replays: newest verified full + its chain in order,
        bitwise-exact (see ``restore``/``load_ps_table``)."""
        if not ps_tables:
            raise ValueError("save_delta: ps_tables is required (a delta "
                             "checkpoint IS the PS-table increment)")
        self.wait()  # one write in flight at a time; surfaces prior errors
        base = self._ps_base
        if base is None:
            raise RuntimeError(
                "save_delta: no committed full checkpoint to anchor the "
                "delta chain — call save(ps_tables=...) (or restore) first")
        base_step = int(base["step"])
        marks: Dict[str, int] = dict(base["marks"])  # type: ignore[arg-type]
        tables_blob: Dict[str, dict] = {}
        on_commit = []
        for tname, table in ps_tables.items():
            hook = getattr(table, "flush_hook", None)
            if hook is not None:
                hook()
            since = int(marks.get(tname, 0))
            mark = int(table.journal_mark())
            entries = [e for e in table.journal_entries_since(since)
                       if e[0] <= mark]
            lanes = int(table.lanes)
            if entries:
                ids = np.concatenate([e[1] for e in entries])
                rows = np.concatenate([e[2] for e in entries], axis=0)
            else:
                ids = np.zeros((0,), np.int64)
                rows = np.zeros((0, lanes), np.uint16)
            tables_blob[tname] = {
                "since_mark": since, "mark": mark,
                "seqs": np.asarray([e[0] for e in entries], np.int64),
                "counts": np.asarray([e[1].shape[0] for e in entries],
                                     np.int64),
                "ids": ids, "rows": rows, "lanes": lanes,
                "vocab": int(table.spec.vocab),
            }
            marks[tname] = mark
            on_commit.append(lambda t=table, m=mark: t.journal_truncate(m))
        on_commit.append(lambda s=base_step, m=dict(marks):
                         self._set_ps_base(s, m))
        vals = {k: np.asarray(v) for k, v in (extra or {}).items()}
        self._thread = threading.Thread(
            target=self._write_delta,
            args=(base_step, int(step), tables_blob, vals, on_commit),
            daemon=True)
        self._thread.start()
        if blocking:
            self.wait()

    def _write_delta(self, base_step: int, step: int, tables_blob: dict,
                     vals: dict, on_commit=()):
        """Writer-thread entry for a delta (same retry/commit contract as
        `_write`: manifest last, on_commit only after it is durable)."""
        retries = int(os.environ.get("PDTPU_CKPT_RETRIES", "3"))
        backoff_ms = float(os.environ.get("PDTPU_CKPT_RETRY_BACKOFF_MS",
                                          "100"))
        attempt = 0
        while True:
            try:
                payload = {"base_step": base_step, "step": step,
                           "tables": tables_blob, "extra": vals}
                path = self._delta_path(base_step, step)
                self._current_path = path
                manifest: Dict[str, dict] = {}
                digest, size = _write_bytes(
                    path + ".tmp", pickle.dumps(payload, protocol=4))
                manifest[os.path.basename(path)] = {"sha256": digest,
                                                    "bytes": size}
                fault_point("ckpt.delta_write", path=path + ".tmp")
                os.replace(path + ".tmp", path)
                mpath = self._delta_manifest_path(base_step, step)
                self._current_path = mpath
                blob = json.dumps({"step": step, "base_step": base_step,
                                   "files": manifest},
                                  sort_keys=True).encode("utf-8")
                _write_bytes(mpath + ".tmp", blob)
                os.replace(mpath + ".tmp", mpath)
                for cb in on_commit:
                    try:
                        cb()
                    except Exception:
                        pass  # commit stands; truncation is best-effort
                return
            except OSError as e:
                path = getattr(e, "filename", None) or self._current_path
                if attempt >= retries:
                    self._error = (e, step, path, attempt)
                    return
                _RETRIES.inc()
                time.sleep(min(backoff_ms * (2 ** attempt), 5000.0) / 1e3)
                attempt += 1
            except BaseException as e:
                self._error = (e, step, self._current_path, attempt)
                return

    def _set_ps_base(self, step: int, marks: Dict[str, int]) -> None:
        self._ps_base = {"step": int(step),
                         "marks": {k: int(v) for k, v in marks.items()}}

    def load_ps_table(self, tname: str):
        """Shard-recovery read path: ``(full_rows, journal_mark, step)``
        for PS table `tname` from the newest checkpoint that passes
        integrity verification. Touches no scope and needs no Program —
        it is called from inside the tier's pull/push threads while the
        training loop is blocked on the dead shard. Deliberately does NOT
        ``wait()`` on an in-flight save: an uncommitted step has no
        manifest yet and simply isn't a candidate."""
        psn = f"{tname}@ps"
        failures: List[str] = []
        for st in sorted(set(self.all_steps()), reverse=True):
            path = self._existing_path(st)
            if path is None:
                continue
            bad = self.verify(st)
            if not bad:
                try:
                    if path.endswith(".ptck"):
                        from ..native import read_bundle
                        bundle = read_bundle(path)
                        if bundle is None:
                            raise RuntimeError(
                                f"cannot read native checkpoint {path}")
                    else:
                        with open(path, "rb") as f:
                            bundle = pickle.load(f)["vars"]
                    assembled = self._assemble_shards(st)
                    if psn not in assembled:
                        raise RuntimeError(f"no {psn!r} shards")
                    mark = int(np.asarray(
                        bundle.get(f"@ps_mark@{tname}", 0)).reshape(()))
                    # replay the verified delta chain: a shard recovered
                    # mid-stream gets full ∘ deltas, and the returned mark
                    # is the last delta's so the client replays only the
                    # journal tail past it
                    rows, mark, _ = self._apply_delta_chain(
                        self._delta_chain(st), tname, assembled[psn], mark)
                    return rows, mark, st
                except (RuntimeError, OSError, EOFError, ValueError,
                        pickle.UnpicklingError) as e:
                    bad = [f"{type(e).__name__}: {e}"]
            failures.append(f"step {st}: {'; '.join(bad)}")
            _FALLBACK.inc()
        raise RuntimeError(
            f"ps recovery: no verifiable checkpoint holding table "
            f"{tname!r} in {self.dirname!r}"
            + (f" ({' | '.join(failures)})" if failures else
               " (no checkpoints at all — save one before training so a "
               "restarted shard has a recovery base)"))

    # -- save --------------------------------------------------------------
    def save(self, step: int, program: Optional[Program] = None,
             scope: Optional[Scope] = None, blocking: bool = False,
             extra: Optional[Dict[str, object]] = None,
             ps_tables: Optional[Dict[str, object]] = None):
        """Snapshot now, write in the background (orbax async-save shape).

        `extra` rides in the bundle verbatim (numpy-converted) — e.g.
        ``@dataio@*`` input-pipeline cursors. Keys should start with ``@``
        so they can never collide with a program variable.

        `ps_tables` ({table_name: ps.ShardedTable}) adds the PS embedding
        tier's shards to the same per-rank shard files + manifest path:
        each shard's slice is dumped NOW (snapshot semantics — flush the
        tier's pushers first) under the ``<name>@ps`` key, one record per
        shard, so a shard's bytes ride the identical tmp→fsync→rename +
        SHA-256 commit protocol as a ZeRO-sharded var. The table's push
        journal mark rides along as ``@ps_mark@<name>`` (read back by
        shard recovery) and the journal is truncated to it once — and
        only once — this checkpoint COMMITS."""
        import jax

        program = program or default_main_program()
        scope = scope or _scope()
        self.wait()  # one write in flight at a time
        vals, shards = _snapshot(program, scope)
        shards = list(shards)
        ps_names = []
        on_commit = []
        ps_marks_now: Dict[str, int] = {}
        for tname, table in (ps_tables or {}).items():
            psn = f"{tname}@ps"
            ps_names.append(psn)
            spec, lanes = table.spec, table.lanes
            hook = getattr(table, "flush_hook", None)
            if hook is not None:
                # flush-before-save: the tier writes back device-resident
                # dirty rows (hot cache) and drains its pusher, so the
                # mark taken below covers every update the dumps contain
                hook()
            if hasattr(table, "journal_mark"):
                # mark BEFORE the dumps: an entry with seq <= mark was
                # applied before the caller's flush, so the dumped bytes
                # contain it; a racing push lands at seq > mark and stays
                # journaled (replay is idempotent either way)
                mark = int(table.journal_mark())
                vals[f"@ps_mark@{tname}"] = np.asarray(mark, np.int64)
                ps_marks_now[tname] = mark
                on_commit.append(
                    lambda t=table, m=mark: t.journal_truncate(m))
            for i in range(spec.num_shards):
                lo, hi = spec.bounds(i)
                shards.append((psn, ((lo, hi), (0, lanes)),
                               (spec.vocab, lanes), "uint16",
                               table.dump_shard(i)))
        if ps_names:
            # a committed full save is the new delta-chain head (the
            # compaction point): subsequent save_delta() calls extend it
            on_commit.append(lambda s=int(step), m=dict(ps_marks_now):
                             self._set_ps_base(s, m))
        rank = jax.process_index()
        if ps_names:
            # restore-side coverage check: which PS tables this
            # checkpoint is supposed to contain
            vals["@ps_manifest@"] = np.asarray("\n".join(sorted(ps_names)))
        if rank == 0:
            # manifest of every sharded var name (ADVICE r3): rank 0 sees
            # the GLOBAL sharding of each array even though it holds only
            # its own addressable shards, so it can record which vars must
            # be fully assembled from the per-rank shard files on restore.
            # Without this, a rank whose index file is missing entirely
            # (crash between rank-0's marker write and a slow rank's
            # background write — there is no cross-rank barrier) could
            # leave a var it exclusively held at its init value, silently.
            sharded = [v.name for v in program.list_vars() if v.persistable
                       and v.name not in vals  # gathered ZeRO state is
                       # already in the bundle — not shard-file material
                       and isinstance(scope.find_var(v.name), jax.Array)
                       and not _is_replicated(scope.find_var(v.name))]
            if sharded:
                vals["@shard_manifest@"] = np.asarray(
                    "\n".join(sorted(sharded)))
        rng = scope.find_var(_RNG_STATE)
        if rng is not None:
            if jax.dtypes.issubdtype(getattr(rng, "dtype", None),
                                     jax.dtypes.prng_key):
                # typed keys can't cross numpy; store raw data + impl name
                vals["@rng@"] = np.asarray(jax.random.key_data(rng))
                vals["@rng_impl@"] = np.asarray(
                    str(jax.random.key_impl(rng)))
            else:
                vals["@rng@"] = np.asarray(rng)
        for k, v in (extra or {}).items():
            vals[k] = np.asarray(v)
        self._thread = threading.Thread(
            target=self._write, args=(step, vals, shards, rank, on_commit),
            daemon=True)
        self._thread.start()
        if blocking:
            self.wait()

    def wait(self):
        """Join the in-flight write; re-raises a writer failure naming the
        step and the failing path (a silently lost checkpoint must not
        look durable)."""
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if self._error is not None:
            (err, step, path, attempts), self._error = self._error, None
            where = f" (path {path!r})" if path else ""
            tried = (f" after {attempts + 1} attempts"
                     if isinstance(err, OSError) and attempts else "")
            raise RuntimeError(
                f"checkpoint write failed at step {step}{where}{tried}"
            ) from err

    # -- restore -----------------------------------------------------------
    def _assemble_shards(self, step: int) -> Dict[str, np.ndarray]:
        """Merge every rank's shard files into full host arrays: works
        under ANY process count / mesh on restore — the reshardable part of
        the contract. Missing coverage raises instead of returning
        silently-partial parameters."""
        out: Dict[str, np.ndarray] = {}
        placed: Dict[str, int] = {}
        for fname in sorted(os.listdir(self.dirname)):
            if not (fname.startswith(f"ckpt-{step}.index-")
                    and fname.endswith(".json")):
                continue
            rank = fname[len(f"ckpt-{step}.index-"):-len(".json")]
            with open(os.path.join(self.dirname, fname)) as f:
                index = json.load(f)
            spath = os.path.join(self.dirname,
                                 f"ckpt-{step}.shards-{rank}.pkl")
            with open(spath, "rb") as f:
                data = pickle.load(f)
            for name, ent in index.items():
                if name not in out:
                    out[name] = np.empty(tuple(ent["shape"],),
                                         dtype=ent["dtype"])
                    placed[name] = 0
                for sh in ent["shards"]:
                    sl = tuple(slice(a, b) for a, b in sh["bounds"])
                    piece = data[sh["key"]]
                    out[name][sl] = piece
                    placed[name] += int(piece.size)
        for name, arr in out.items():
            if placed[name] < arr.size:
                raise RuntimeError(
                    f"checkpoint step {step}: sharded var {name!r} has only "
                    f"{placed[name]}/{arr.size} elements across the rank "
                    f"index files — a rank's shard file is missing")
        return out

    def _load_step(self, step: int, path: str, program: Program):
        """Read + assemble one checkpoint WITHOUT touching the scope: any
        read error or shard-coverage gap surfaces here, before a single
        var is mutated, so the fallback walk never leaves the scope
        half-restored."""
        if path.endswith(".ptck"):
            from ..native import read_bundle
            bundle = read_bundle(path)
            if bundle is None:
                raise RuntimeError(f"cannot read native checkpoint {path}")
            bundle.pop("@step@", None)
            vars_ = bundle
        else:
            with open(path, "rb") as f:
                vars_ = pickle.load(f)["vars"]
        names = {v.name for v in program.list_vars() if v.persistable}
        manifest_raw = vars_.pop("@shard_manifest@", None)
        ps_manifest_raw = vars_.pop("@ps_manifest@", None)
        assembled = self._assemble_shards(step)
        if manifest_raw is not None:
            # backends may round-trip the string as a 0-d or 1-element array
            raw = np.asarray(manifest_raw).ravel()
            expected = set("\n".join(str(x) for x in raw).split("\n"))
            missing = sorted((expected & names) - set(assembled))
            if missing:
                raise RuntimeError(
                    f"checkpoint step {step}: sharded vars {missing} are in "
                    "the save-time manifest but absent from every rank's "
                    "index file — a rank's shard/index files are missing "
                    "(e.g. crash between rank-0's marker write and that "
                    "rank's background shard write)")
        if ps_manifest_raw is not None:
            raw = np.asarray(ps_manifest_raw).ravel()
            expected_ps = set("\n".join(str(x) for x in raw).split("\n"))
            missing_ps = sorted(expected_ps - set(assembled))
            if missing_ps:
                raise RuntimeError(
                    f"checkpoint step {step}: PS tables {missing_ps} are "
                    "in the save-time manifest but absent from every "
                    "rank's index file — the shard files are missing")
        # `@ps`-suffixed names are never program vars, so the `n in names`
        # filter below keeps them out of the scope; they flow back through
        # the fourth return for ShardedTable.load_full
        to_set = {n: arr for n, arr in vars_.items() if n in names}
        to_set.update({n: a for n, a in assembled.items() if n in names})
        rng_key = None
        if "@rng@" in vars_:  # resume the random stream too
            import jax
            import jax.numpy as jnp
            raw = vars_["@rng@"]
            impl = vars_.get("@rng_impl@")
            if impl is not None:
                rng_key = jax.random.wrap_key_data(jnp.asarray(raw),
                                                   impl=str(impl))
            else:
                rng_key = jnp.asarray(raw)
        extra = {k: v for k, v in vars_.items() if k.startswith("@dataio@")}
        ps_marks = {k[len("@ps_mark@"):]: int(np.asarray(v).reshape(()))
                    for k, v in vars_.items()
                    if k.startswith("@ps_mark@")}
        return to_set, rng_key, extra, assembled, ps_marks

    def restore(self, step: Optional[int] = None,
                program: Optional[Program] = None,
                scope: Optional[Scope] = None,
                ps_tables: Optional[Dict[str, object]] = None
                ) -> Optional[int]:
        """Load a checkpoint into the scope as host arrays; the next
        compiled step lifts them into the current mesh's shardings — save
        under dp=8, restore under dp=4×tp=2 just works.

        With ``step=None`` the newest checkpoint that passes integrity
        verification wins: a corrupt/torn candidate is skipped with a
        warning naming the bad files (``checkpoint/fallback_steps``
        counter), and the walk continues to older steps. Only when EVERY
        candidate fails does restore raise. An explicit ``step`` is loaded
        or fails — no silent substitution.

        `ps_tables` ({table_name: ps.ShardedTable}) restores PS embedding
        shards too: the checkpoint's ``<name>@ps`` slices are assembled
        into the full table and re-partitioned onto each table's LIVE
        range spec — restoring onto a different shard count than the save
        just works. Coverage is validated BEFORE the scope or any shard is
        mutated; a candidate missing a requested table falls back like any
        other integrity failure."""
        program = program or default_main_program()
        scope = scope or _scope()
        self.wait()
        self.last_extra = {}
        if step is not None:
            candidates = [step]
        else:
            candidates = sorted(set(self.all_steps()), reverse=True)
        failures: List[str] = []
        for st in candidates:
            path = self._existing_path(st)
            if path is None:
                continue
            bad = self.verify(st)
            loaded = None
            if not bad:
                try:
                    loaded = self._load_step(st, path, program)
                except (RuntimeError, OSError, EOFError, ValueError,
                        pickle.UnpicklingError) as e:
                    bad = [f"{os.path.basename(path)}: "
                           f"{type(e).__name__}: {e}"]
            if not bad and loaded is not None and ps_tables:
                # every requested table must be fully present with the
                # right geometry before ANY state mutates
                assembled = loaded[3]
                for tname, table in ps_tables.items():
                    psn = f"{tname}@ps"
                    want = (table.spec.vocab, table.lanes)
                    if psn not in assembled:
                        bad.append(f"PS table {tname!r}: no {psn!r} "
                                   "shards in this checkpoint")
                    elif assembled[psn].shape != want:
                        bad.append(
                            f"PS table {tname!r}: checkpoint shape "
                            f"{assembled[psn].shape} != live {want}")
            if bad:
                desc = "; ".join(bad)
                failures.append(f"step {st}: {desc}")
                _FALLBACK.inc()
                warnings.warn(
                    f"checkpoint step {st} in {self.dirname!r} failed "
                    f"integrity verification ({desc}); falling back to the "
                    "next older checkpoint", RuntimeWarning)
                continue
            to_set, rng_key, extra, assembled, ps_marks = loaded
            # incremental checkpoints: replay this full save's verified
            # delta chain onto the assembled PS tables BEFORE any state
            # mutates — the restored bytes are full ∘ deltas, bitwise
            # identical to the table at the last committed save_delta
            chain = self._delta_chain(st) if ps_tables else []
            final_marks: Dict[str, int] = {}
            for tname in (ps_tables or {}):
                rows, fmark, _ = self._apply_delta_chain(
                    chain, tname, assembled[f"{tname}@ps"],
                    int(ps_marks.get(tname, 0)))
                assembled[f"{tname}@ps"] = rows
                final_marks[tname] = fmark
            for payload in chain:
                extra.update({k: v for k, v in
                              (payload.get("extra") or {}).items()
                              if k.startswith("@dataio@")})
            for n, arr in to_set.items():
                scope.set_var(n, arr)
            if rng_key is not None:
                scope.set_var(_RNG_STATE, rng_key)
            for tname, table in (ps_tables or {}).items():
                table.load_full(assembled[f"{tname}@ps"])
                if hasattr(table, "journal_reset"):
                    # the live journal (possibly from another process
                    # lifetime) no longer describes deltas over what was
                    # just loaded; re-anchor it at the restored mark —
                    # the last applied delta's, else the full save's
                    table.journal_reset(int(final_marks.get(tname, 0)))
            if ps_tables:
                # restore re-anchors the delta chain: new deltas extend
                # from exactly the state just loaded
                self._set_ps_base(st, final_marks)
            self.last_extra = extra
            return st
        if failures:
            raise RuntimeError(
                f"no verifiable checkpoint in {self.dirname!r}; every "
                "candidate failed integrity verification: "
                + " | ".join(failures))
        return None


def save_checkpoint(dirname: str, step: int, program=None, scope=None,
                    blocking: bool = True):
    ck = Checkpointer(dirname)
    ck.save(step, program=program, scope=scope, blocking=blocking)
    return ck


def load_checkpoint(dirname: str, program=None, scope=None,
                    step: Optional[int] = None):
    return Checkpointer(dirname).restore(step, program=program, scope=scope)

"""Checkpoint serialization and the latest/best artifact contract.

Replaces ``torch.save(state, 'latest.pt')`` / ``torch.load(...,
map_location='cpu')`` (D4; ``restnet_ddp.py:45,127-132,150``) with an atomic
msgpack pytree checkpoint:

- one canonical layout shared by every parallelism mode (the reference keeps
  this invariant by always saving the unwrapped ``model.module.state_dict()``,
  ``restnet_ddp.py:38``): ``{state: TrainState pytree, epoch, step,
  best_acc}`` — restores from a 1-chip run onto a pod and back;
- atomic: write to a temp file in the same directory, fsync, rename — a
  preemption mid-write can never corrupt ``latest.ckpt`` (torch.save has the
  same failure mode the reference ignores);
- rank-0-gated by the caller (ref ``restnet_ddp.py:36,145``) — parameters
  are replicated, so one host's copy is the global truth;
- optional background-thread save so the step loop doesn't stall on disk
  (the suspend path saves synchronously — it's about to yield anyway).

Artifacts mirror the reference: ``latest.ckpt`` = full training state,
written on suspend (not periodic — same policy, SURVEY.md §5);
``best.ckpt`` = written on validation improvement (``restnet_ddp.py:145-150``).
"""

from __future__ import annotations

import os
import re
import threading
from typing import Any, Optional

import jax
import numpy as np
from flax import serialization

from pytorch_distributed_tpu.resilience.faults import fault_point
from pytorch_distributed_tpu.resilience.retry import retry_call
from pytorch_distributed_tpu.telemetry import spans

LATEST = "latest.ckpt"
BEST = "best.ckpt"


def gather_global(tree: Any) -> Any:
    """Materialize every leaf as a host numpy array of the GLOBAL value.

    Locally-readable leaves (fully addressable, or fully replicated across
    hosts) are a straight ``device_get``. A leaf SHARDED across processes
    (multi-host TP/EP/FSDP) is gathered with ``process_allgather`` — a
    COLLECTIVE: every process in the job must call ``gather_global``
    together, even ranks that will discard the result. The trainer
    therefore builds checkpoint payloads on all ranks and gates only the
    disk write on rank 0 (``restnet_ddp.py:36,145`` semantics). For plain
    replicated DP (every reference mode) no collective runs and this is
    exactly the old fast path.
    """

    def leaf_to_host(x):
        if _needs_gather(x):
            from jax.experimental import multihost_utils

            return np.asarray(multihost_utils.process_allgather(x, tiled=True))
        return np.asarray(jax.device_get(x))

    return jax.tree.map(leaf_to_host, tree)


def _needs_gather(x) -> bool:
    """True for arrays whose global value is NOT locally readable: sharded
    across processes and not replicated. Fully-replicated multi-host arrays
    are readable from any single process (``device_get`` uses the local
    copy), so plain multi-host DP never needs the collective."""
    return (
        isinstance(x, jax.Array)
        and not x.is_fully_addressable
        and not x.is_fully_replicated
    )


def _owned_host_copy(x) -> np.ndarray:
    """Host numpy array that OWNS its memory. On TPU ``device_get``
    already copies; on the CPU backend ``np.asarray(jax_array)`` returns a
    zero-copy VIEW of the live buffer — which the next donated train step
    would reuse under a background writer's feet. Copy whenever numpy
    doesn't own the data."""
    arr = np.asarray(x)
    if not arr.flags["OWNDATA"] and not isinstance(x, np.ndarray):
        arr = np.array(arr)
    return arr


def _to_host(tree: Any) -> Any:
    """Host-side snapshot for serialization. NOT a collective: leaves must
    be locally readable (pass trees through ``gather_global`` first in
    multi-host sharded runs — calling this from a rank-gated branch with
    cross-process-sharded arrays would otherwise hang the job in a
    one-sided collective)."""

    def leaf_to_host(x):
        if _needs_gather(x):
            raise ValueError(
                "checkpoint payload contains an array sharded across "
                "processes; gather it on ALL processes with "
                "utils.checkpoint.gather_global(tree) before the rank-0 "
                "save call (process_allgather is a collective)."
            )
        return _owned_host_copy(x)

    return jax.tree.map(leaf_to_host, tree)


def save_checkpoint(path: str | os.PathLike, payload: Any) -> None:
    """Atomically serialize a pytree payload to ``path``."""
    path = os.fspath(path)
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    state_dict = serialization.to_state_dict(_to_host(payload))
    blob = serialization.msgpack_serialize(state_dict)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "wb") as f:
        f.write(blob)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)


def load_checkpoint(path: str | os.PathLike, template: Any) -> Any:
    """Restore a payload saved by ``save_checkpoint`` into the structure of
    ``template`` (≙ ``load_state_dict``, ``restnet_ddp.py:128-132``).
    Arrays come back as numpy on host — the trainer re-places them onto the
    mesh with the right sharding (≙ ``map_location='cpu'`` then ``.cuda()``).
    """
    with open(os.fspath(path), "rb") as f:
        state_dict = serialization.msgpack_restore(f.read())
    return serialization.from_state_dict(template, state_dict)


MANIFEST = "manifest.json"

# shard-<token>-NNNNN.npz (current) or shard-NNNNN.npz (pre-r4 legacy)
_SHARD_RE = re.compile(r"^shard-(?:([0-9a-f]+)-)?(\d{5})\.npz$")


def _shard_name(token: str, pidx: int) -> str:
    return f"shard-{token}-{pidx:05d}.npz"


def _tree_paths(tree):
    import jax.tree_util as jtu

    flat, treedef = jtu.tree_flatten_with_path(tree)
    paths = []
    for path, leaf in flat:
        parts = []
        for p in path:
            name = getattr(p, "key", None)
            if name is None:
                name = getattr(p, "name", None)
            if name is None:
                name = str(getattr(p, "idx", p))
            parts.append(str(name))
        paths.append("/".join(parts))
    return paths, [leaf for _, leaf in flat], treedef


def _check_unique_paths(paths, where: str) -> None:
    """Two distinct leaves flattening to one path string (a dict key
    containing '/', or an int key colliding with a name) would silently
    share one manifest entry and corrupt the second leaf on restore."""
    if len(set(paths)) != len(paths):
        from collections import Counter

        dups = sorted(p for p, c in Counter(paths).items() if c > 1)
        raise ValueError(
            f"{where}: pytree flattens to duplicate leaf paths {dups!r} "
            "(a '/' inside a dict key collides with the path separator); "
            "rename the offending keys"
        )


def _payload_mesh_meta(leaves) -> Optional[dict]:
    """``{"axes": [...], "shape": [...]}`` of the mesh the payload's
    arrays live on (the first ``NamedSharding`` leaf wins — one payload is
    placed on one mesh), or None for host-only payloads. Recorded in the
    manifest so a restore onto a different topology is detectable."""
    for leaf in leaves:
        sharding = getattr(leaf, "sharding", None)
        mesh = getattr(sharding, "mesh", None)
        axis_names = getattr(mesh, "axis_names", None)
        if axis_names:
            return {
                "axes": [str(a) for a in axis_names],
                "shape": [int(mesh.shape[a]) for a in axis_names],
            }
    return None


def _canonical_blocks(x: jax.Array):
    """Deterministic global block layout of a jax.Array: one canonical
    owner device per distinct index tuple. Ownership round-robins over the
    processes holding replicas of each block (a min-device-id rule would
    pile every replicated block onto process 0 — the model axis is the
    innermost, so process 0 holds a replica of everything). Every process
    computes the SAME layout from sharding metadata alone — that is what
    lets rank 0 write a complete manifest without any communication."""
    groups: dict = {}
    for dev, idx in x.sharding.devices_indices_map(x.shape).items():
        key = tuple(
            (sl.start or 0, sl.stop if sl.stop is not None else dim)
            for sl, dim in zip(idx, x.shape)
        )
        groups.setdefault(key, []).append(dev)
    owners = {}
    for i, (key, devs) in enumerate(sorted(groups.items())):
        procs = sorted({d.process_index for d in devs})
        proc = procs[i % len(procs)]
        owners[key] = min(
            (d for d in devs if d.process_index == proc), key=lambda d: d.id
        )
    return owners  # {((start, stop), ...): owner_device}


class _Arena:
    """Reusable host snapshot buffer for sharded saves.

    The snapshot must COPY every local block (the live buffers are donated
    into the next train step), and on this kernel first-touch page faults
    dominate that copy: 377 separate leaf allocations held live measured
    12.4 s for a 1.5 GB state, vs 0.65 s for the same copies into reused
    pages (4 KB write-faults run ~100 MB/s here once the process maps
    jax's heap; MAP_POPULATE makes it WORSE — it pre-faults the private
    mapping read-only against the zero page and every write still CoW
    faults). One arena with ``MADV_HUGEPAGE`` (THP is in madvise mode)
    faults at 2 MB granularity — measured ~1 s/1.5 GB first fill — and
    the ``Checkpointer`` reuses it across saves, so steady-state
    best-save stalls are pure memcpy (~0.3 s/1.5 GB)."""

    def __init__(self):
        self._mm = None
        self._size = 0

    def ensure(self, nbytes: int) -> np.ndarray:
        if nbytes > self._size or self._mm is None:
            import mmap

            self._mm = mmap.mmap(
                -1, max(nbytes, 1),
                flags=mmap.MAP_PRIVATE | mmap.MAP_ANONYMOUS,
            )
            if hasattr(self._mm, "madvise") and hasattr(mmap, "MADV_HUGEPAGE"):
                self._mm.madvise(mmap.MADV_HUGEPAGE)
            self._size = max(nbytes, 1)
        return np.frombuffer(self._mm, np.uint8, count=self._size)

    def warm(self, nbytes: int) -> None:
        """Pre-fault ``nbytes`` of arena by dirtying every page. The fault
        cost is unavoidable ONCE per arena growth (~10 s/1.5 GB on this
        kernel even with THP — compaction stalls); trainers run this on a
        background thread at init, overlapped with the first XLA compile,
        so even the FIRST non-blocking save stalls only for the memcpy."""
        buf = self.ensure(nbytes)
        buf[0::4096] = 1  # one write per 4 KB page


class _ShardedSave:
    """One in-flight sharded save, split into three stages so the step
    loop only pays for the first:

    1. ``__init__`` — SNAPSHOT (synchronous, collective): broadcast-agree
       the save token, compute the block layout + manifest from sharding
       metadata, and ``device_get`` this process's blocks to host numpy.
       This must happen before the trainer's next step because the state
       arrays are donated into it.
    2. ``write`` — pure file I/O (token-named shard file, tmp+rename);
       safe on a background thread. A save NEVER overwrites the previous
       checkpoint's data files: they are named by the OLD token and stay
       referenced by the OLD manifest until step 3 replaces it — a crash
       any time before then leaves the previous checkpoint fully
       restorable (the durability fix over the r3 in-place layout).
    3. ``finalize`` — MAIN THREAD ONLY (cross-host barriers are jax
       collectives): barrier on the data files, rank-0 atomic manifest
       replace (the commit point), barrier, then GC this process's
       stale-token shard files.

    ``save_sharded`` runs all three synchronously;
    ``Checkpointer.save_*_sharded(block=False)`` runs 2 on a thread and
    defers 3 to ``Checkpointer.wait()`` — which every rank reaches at the
    same collective-ordered point (epoch end / suspend / next save).
    """

    def __init__(self, dirpath: str | os.PathLike, payload: Any,
                 arena: Optional[_Arena] = None, snapshot: bool = True):
        self.dirpath = os.fspath(dirpath)
        if os.path.isfile(self.dirpath):
            try:  # a legacy single-file checkpoint of the same name; every
                os.remove(self.dirpath)  # process races on shared fs — one wins
            except FileNotFoundError:
                pass
        os.makedirs(self.dirpath, exist_ok=True)
        self.pidx = jax.process_index()

        # Save token: names this save's files and guards against TORN
        # saves (manifest written LAST records it; load refuses any
        # manifest-referenced file carrying a different token). Agreed via
        # broadcast so it needs no shared clock.
        token = os.urandom(8).hex()
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils

            token_arr = np.frombuffer(bytes.fromhex(token), np.uint8)
            token = bytes(
                np.asarray(
                    multihost_utils.broadcast_one_to_all(token_arr)
                ).tobytes()
            ).hex()
        self.token = token
        self.fname = _shard_name(token, self.pidx)

        paths, leaves, _ = _tree_paths(payload)
        _check_unique_paths(paths, "save_sharded")
        mesh_meta = _payload_mesh_meta(leaves)

        # Pass 1 — metadata only: block layout + manifest + the list of
        # local blocks to snapshot (no copies yet).
        specs: list = []  # (key, src, shape, np.dtype)
        manifest: dict[str, Any] = {"version": 2,
                                    "n_processes": jax.process_count(),
                                    "leaves": {}}
        for path, leaf in zip(paths, leaves):
            # Block-decompose every non-replicated array (not just the
            # cross-process ones): the single-process save then exercises
            # the same layout/assembly path the pod uses, and blocks never
            # exceed one device's shard.
            if (
                isinstance(leaf, jax.Array)
                and leaf.ndim > 0
                and not leaf.is_fully_replicated
            ):
                layout = _canonical_blocks(leaf)
                local = {
                    tuple(
                        (sl.start or 0,
                         sl.stop if sl.stop is not None else dim)
                        for sl, dim in zip(sh.index, leaf.shape)
                    ): sh
                    for sh in leaf.addressable_shards
                }
                blocks = []
                for i, (key, dev) in enumerate(sorted(layout.items())):
                    entry = {
                        "file": _shard_name(token, dev.process_index),
                        "key": f"{path}#{i}",
                        "start": [s for s, _ in key],
                        "stop": [e for _, e in key],
                    }
                    blocks.append(entry)
                    if dev.process_index == self.pidx:
                        specs.append((
                            entry["key"], local[key].data,
                            tuple(e - s for s, e in key),
                            np.dtype(leaf.dtype),
                        ))
                arr_like = leaf
            else:
                arr_like = (
                    leaf if isinstance(leaf, jax.Array) else np.asarray(leaf)
                )
                blocks = [{
                    "file": _shard_name(token, 0),
                    "key": f"{path}#0",
                    "start": [0] * arr_like.ndim,
                    "stop": list(arr_like.shape),
                }]
                if self.pidx == 0:
                    specs.append((
                        f"{path}#0", arr_like, tuple(arr_like.shape),
                        np.dtype(arr_like.dtype),
                    ))
            manifest["leaves"][path] = {
                "dtype": str(np.dtype(arr_like.dtype)),
                "shape": list(arr_like.shape),
                "blocks": blocks,
            }
        manifest["token"] = token
        if mesh_meta is not None:
            # writer topology, for elastic resume: lets a restore onto a
            # DIFFERENT mesh shape announce itself (reshard/) and lets
            # tools refuse/permit cross-topology restores explicitly.
            # Absent for host-only payloads and pre-round-9 checkpoints.
            manifest["mesh"] = mesh_meta
        self.manifest = manifest

        # Pass 2 — SNAPSHOT: one bulk copy of every local block into a
        # single (reusable) arena. The copy is mandatory for the
        # NON-BLOCKING path — the live buffers are donated into the next
        # train step, and on the CPU backend ``np.asarray(jax_array)`` is
        # a zero-copy view of them. See ``_Arena`` for why one buffer
        # instead of per-leaf copies. BLOCKING saves (``snapshot=False``)
        # skip the copy entirely and stream straight from the sources in
        # ``write()``: the caller cannot run its next (donating) step
        # until the save returns, so there is nothing to race — this
        # removes both the memcpy and the arena's first-touch page-fault
        # cost (~10 s/1.5 GB cold, memory notes in ``_Arena``) from the
        # suspend path.
        if not snapshot:
            self.my_blocks = {
                key: src for key, src, _shape, _dtype in specs
            }
            self._arena_buf = None
            self._thread: Optional[threading.Thread] = None
            self._write_err: Optional[BaseException] = None
            self._done = False
            return
        total = 0
        offs = []
        for _key, _src, shape, dtype in specs:
            total = -(-total // 128) * 128  # 128-byte align each block
            offs.append(total)
            total += int(np.prod(shape, dtype=np.int64)) * dtype.itemsize
        self._arena_buf = (arena or _Arena()).ensure(total)
        my_blocks: dict[str, np.ndarray] = {}
        for (key, src, shape, dtype), off in zip(specs, offs):
            nb = int(np.prod(shape, dtype=np.int64)) * dtype.itemsize
            dst = self._arena_buf[off:off + nb].view(dtype).reshape(shape)
            np.copyto(dst, np.asarray(src))
            my_blocks[key] = dst
        self.my_blocks = my_blocks
        self._thread: Optional[threading.Thread] = None
        self._write_err: Optional[BaseException] = None
        self._done = False

    def write(self) -> None:
        """Write this process's token-named shard file. Pure file I/O —
        thread-safe, no jax calls. Transient I/O errors are retried with
        bounded backoff (each attempt rewrites the tmp file from the still
        -held snapshot, so a partial attempt is never published)."""
        retry_call(self._write_once, what=f"shard write {self.fname}")
        self.my_blocks = {}  # release the host snapshot

    def _write_once(self) -> None:
        # raw byte views (bf16 etc. have no numpy descr; the manifest
        # carries the true dtype) — np.savez streams each buffer to disk
        fname = os.path.join(self.dirpath, self.fname)
        tmp = f"{fname}.tmp.{os.getpid()}"
        with open(tmp, "wb") as f:
            np.savez(
                f,
                __token__=np.frombuffer(
                    bytes.fromhex(self.token), np.uint8
                ),
                **{
                    # np.asarray: no-snapshot blocks are still live jax
                    # arrays (or numpy scalars) at write time
                    k: np.ascontiguousarray(np.asarray(v))
                    .reshape(-1).view(np.uint8)
                    for k, v in self.my_blocks.items()
                },
            )
            f.flush()
            os.fsync(f.fileno())
        # mid-shard-write hazard: the tmp file is complete but the shard
        # is not published — a kill here must leave the previous
        # checkpoint's manifest + files fully restorable
        fault_point("ckpt.shard_write")
        os.replace(tmp, fname)

    def _write_guarded(self) -> None:
        try:
            self.write()
        except BaseException as e:  # surfaced at finalize()
            self._write_err = e  # jaxlint: disable=thread-unsynced-mutation -- single-owner handoff: finalize() joins the writer thread before reading, so the store happens-before the only read

    def start(self) -> None:
        self._thread = threading.Thread(target=self._write_guarded,
                                        daemon=True)
        self._thread.start()

    def finalize(self) -> None:
        """Join the writer, barrier, commit the manifest, GC stale files.
        Call from the MAIN thread on every process at the same
        collectively-ordered point."""
        import json

        if self._done:
            return
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if self._write_err is not None:
            raise self._write_err

        if jax.process_count() > 1:
            from jax.experimental import multihost_utils

            # all data files on disk BEFORE the manifest makes them live
            multihost_utils.sync_global_devices(
                f"ckpt-data:{self.dirpath}:{self.token}"
            )

        if self.pidx == 0:
            # THE commit point: os.replace is atomic, and the old
            # manifest's files are untouched until the GC below.
            mtmp = os.path.join(self.dirpath,
                                f"{MANIFEST}.tmp.{os.getpid()}")
            with open(mtmp, "w") as f:
                json.dump(self.manifest, f)
                f.flush()
                os.fsync(f.fileno())
            # pre-commit hazard: every data file landed, manifest not yet
            # replaced — a kill here must restore the OLD checkpoint
            fault_point("ckpt.pre_commit")
            os.replace(mtmp, os.path.join(self.dirpath, MANIFEST))
            # post-commit hazard: the new checkpoint is live but stale-
            # token GC has not run — a kill here must restore the NEW one
            fault_point("ckpt.post_commit")

        if jax.process_count() > 1:
            from jax.experimental import multihost_utils

            multihost_utils.sync_global_devices(
                f"ckpt:{self.dirpath}:{self.token}"
            )

        # GC: every process removes ITS OWN rank's shard files from
        # superseded saves (older tokens + pre-r4 tokenless names) and any
        # orphaned tmp files. Only after the commit barrier — a reader
        # before it was reading the old manifest's files.
        for name in os.listdir(self.dirpath):
            m = _SHARD_RE.match(name)
            stale_shard = (
                m is not None
                and int(m.group(2)) == self.pidx
                and (m.group(1) or "") != self.token
            )
            stale_tmp = (
                f".npz.tmp." in name
                and f"-{self.pidx:05d}.npz.tmp." in name
                and not name.startswith(f"shard-{self.token}-")
            )
            if stale_shard or stale_tmp:
                try:
                    os.remove(os.path.join(self.dirpath, name))
                except OSError:
                    pass
        self._done = True


def save_sharded(dirpath: str | os.PathLike, payload: Any) -> None:
    """Per-process sharded checkpoint: NO process materializes the global
    state (the scaling fix for ``gather_global``'s full host gather —
    VERDICT r2 missing #5).

    Layout: ``<dirpath>/shard-<token>-NNNNN.npz`` (uncompressed zip of raw
    block buffers — msgpack measured 8.7x slower than the disk) holds the
    blocks whose canonical owner device lives on process NNNNN;
    ``manifest.json`` (rank 0, written last, atomic replace) records every
    leaf's dtype/shape and block table, computed from sharding metadata
    identically on every process. Replicated leaves, numpy arrays, and
    scalars are rank-0-owned single blocks. COLLECTIVE in the weak sense:
    every process must call it (each writes its own file); a cross-host
    barrier before the manifest guarantees all files landed. Atomic at
    CHECKPOINT granularity: files are token-named, so a crash mid-save
    leaves the previous save's manifest + files intact and restorable
    (see ``_ShardedSave``). Synchronous; for the non-stalling trainer
    path use ``Checkpointer.save_*_sharded(block=False)`` + ``wait()``.
    """
    s = _ShardedSave(dirpath, payload, snapshot=False)
    s.write()
    s.finalize()


class _RawNpz:
    """Zero-copy reader for the uncompressed ``.npz`` files ``np.savez``
    writes: mmap the zip once, resolve each member's raw-data offset from
    the local file headers, and serve members as ``np.frombuffer`` views.
    Skips the per-member stream+CRC pass ``np.load`` does — restore cost
    becomes the assembly copies / ``device_put`` alone, with cold pages
    faulted in by the kernel during the copy. Views are READ-ONLY;
    ``load_sharded`` copies on any path that hands arrays to the caller
    unsharded. Raises on anything unexpected (compressed members, odd npy
    headers); the caller falls back to ``np.load``."""

    def __init__(self, path: str):
        import mmap
        import zipfile

        self._f = open(path, "rb")
        self._mm = mmap.mmap(self._f.fileno(), 0, access=mmap.ACCESS_READ)
        self._members: dict[str, tuple[int, int]] = {}
        with zipfile.ZipFile(self._f) as zf:
            for info in zf.infolist():
                if info.compress_type != zipfile.ZIP_STORED:
                    raise ValueError("compressed member")
                ho = info.header_offset
                if self._mm[ho:ho + 4] != b"PK\x03\x04":
                    raise ValueError("bad local header")
                # local-header extra field length can differ from the
                # central directory's — read it from the local header
                fn = int.from_bytes(self._mm[ho + 26:ho + 28], "little")
                ex = int.from_bytes(self._mm[ho + 28:ho + 30], "little")
                name = info.filename
                if name.endswith(".npy"):
                    name = name[:-4]
                self._members[name] = (ho + 30 + fn + ex, info.file_size)

    def __contains__(self, key: str) -> bool:
        return key in self._members

    def __getitem__(self, key: str) -> np.ndarray:
        import io

        try:
            off, size = self._members[key]
            bio = io.BytesIO(self._mm[off:min(off + 4096, off + size)])
            version = np.lib.format.read_magic(bio)
            if version == (1, 0):
                shape, fortran, dtype = (
                    np.lib.format.read_array_header_1_0(bio)
                )
            elif version == (2, 0):
                shape, fortran, dtype = (
                    np.lib.format.read_array_header_2_0(bio)
                )
            else:
                raise ValueError(f"npy version {version}")
            if fortran:
                raise ValueError("fortran-order member")
            if bio.tell() >= 4096:
                raise ValueError("npy header exceeds the 4096-byte window")
            count = int(np.prod(shape)) if shape else 1
            arr = np.frombuffer(
                self._mm, dtype=dtype, count=count, offset=off + bio.tell()
            )
            return arr.reshape(shape)
        except KeyError:
            raise
        except Exception:
            # Constructor-time validation can't see per-member npy
            # quirks (format 3.0, oversized headers): fall back to a
            # lazy np.load for THIS file rather than failing the
            # restore (ADVICE r4 #1).
            if not hasattr(self, "_np_fallback"):
                self._np_fallback = np.load(
                    self._f.name, allow_pickle=False
                )
            return self._np_fallback[key]


class ManifestReader:
    """Block-table access to one sharded checkpoint directory.

    The engine behind :func:`load_sharded` and the ``reshard/`` subsystem:
    parses the manifest once, opens shard files through the mmap-backed
    zero-copy zip reader (``_RawNpz``, with the ``np.load`` fall-through
    and save-token verification), and assembles ANY ``[start, stop)``
    region of any leaf from the blocks that overlap it — the primitive
    that makes restore independent of the mesh that wrote the checkpoint.
    Regions are cached (``make_array_from_callback`` asks once per
    addressable device; replicated leaves repeat identical regions).

    Counters (for restore telemetry / the reshard bench): ``exact_blocks``
    regions served by the no-copy exact-match fast path,
    ``assembled_regions`` regions stitched from partially-overlapping
    blocks, ``bytes_assembled`` copied in doing so.
    """

    def __init__(self, dirpath: str | os.PathLike):
        import json

        self.dirpath = os.fspath(dirpath)
        with open(os.path.join(self.dirpath, MANIFEST)) as f:
            self.manifest = json.load(f)
        self.token = self.manifest.get("token")
        self._shard_cache: dict[str, Any] = {}
        self._region_cache: dict = {}
        self.exact_blocks = 0
        self.assembled_regions = 0
        self.bytes_assembled = 0

    @property
    def mesh_meta(self) -> Optional[dict]:
        """Writer topology ``{"axes": [...], "shape": [...]}`` or None
        (host-only payload / pre-round-9 checkpoint)."""
        return self.manifest.get("mesh")

    def leaf_paths(self) -> list:
        return list(self.manifest.get("leaves", {}))

    def leaf_meta(self, path: str) -> dict:
        meta = self.manifest.get("leaves", {}).get(path)
        if meta is None:
            raise KeyError(
                f"checkpoint at {self.dirpath} has no leaf {path!r}; the "
                "template's structure must match the saved payload"
            )
        return meta

    def _file(self, fname):
        if fname not in self._shard_cache:
            fpath = os.path.join(self.dirpath, fname)
            try:
                npz = _RawNpz(fpath)
            except OSError:
                # transient read failure (cluster fs): bounded retry before
                # falling back; np.load below re-raises hard failures
                npz = retry_call(
                    np.load, fpath, allow_pickle=False,
                    what=f"checkpoint read {fname}",
                )
            except Exception:
                # NpzFile is lazy: only members actually accessed are read
                npz = np.load(fpath, allow_pickle=False)
            if self.token is not None:
                got = bytes(np.asarray(npz["__token__"]).tobytes()).hex()
                if got != self.token:
                    raise RuntimeError(
                        f"torn checkpoint at {self.dirpath}: {fname} "
                        f"belongs to save {got}, manifest says "
                        f"{self.token} — a crash interrupted a save; "
                        "restore an older checkpoint"
                    )
            self._shard_cache[fname] = npz
        return self._shard_cache[fname]

    def _block(self, meta, b) -> np.ndarray:
        bshape = [e - s for s, e in zip(b["start"], b["stop"])]
        return (
            self._file(b["file"])[b["key"]]
            .view(np.dtype(meta["dtype"]))
            .reshape(bshape)
        )

    def read_region(self, path: str, start, stop) -> np.ndarray:
        """Assemble ``[start, stop)`` of leaf ``path`` from overlapping
        blocks (cached). Exact block matches are zero-copy mmap views —
        READ-ONLY; callers handing arrays out unsharded must copy."""
        key = (path, tuple(start), tuple(stop))
        if key not in self._region_cache:
            self._region_cache[key] = self._read_region(
                self.leaf_meta(path), start, stop
            )
        return self._region_cache[key]

    def _read_region(self, meta, start, stop):
        for b in meta["blocks"]:
            if b["start"] == list(start) and b["stop"] == list(stop):
                # exact-match fast path (the writer's sharding and the
                # reader's agree on this region): no assembly copy
                self.exact_blocks += 1
                return self._block(meta, b)
        out = np.empty(
            [e - s for s, e in zip(start, stop)], np.dtype(meta["dtype"])
        )
        for b in meta["blocks"]:
            lo = [max(s, bs) for s, bs in zip(start, b["start"])]
            hi = [min(e, be) for e, be in zip(stop, b["stop"])]
            if any(l >= h for l, h in zip(lo, hi)):
                continue
            block = self._block(meta, b)
            src = tuple(
                slice(l - bs, h - bs)
                for l, h, bs in zip(lo, hi, b["start"])
            )
            dst = tuple(
                slice(l - s, h - s) for l, h, s in zip(lo, hi, start)
            )
            out[dst] = block[src] if out.ndim else block
        self.assembled_regions += 1
        self.bytes_assembled += out.nbytes
        return out


def load_sharded(
    dirpath: str | os.PathLike, template: Any, shardings: Any = None,
    reader: Optional[ManifestReader] = None,
) -> Any:
    """Restore a ``save_sharded`` directory into ``template``'s structure.

    With a ``shardings`` pytree (template-shaped, leaves
    ``jax.sharding.Sharding`` or None), array leaves are built with
    ``jax.make_array_from_callback`` reading ONLY the blocks overlapping
    each local device shard — no process assembles a full copy of a
    sharded leaf, whether or not the target sharding matches the layout
    the writer used (cross-mesh restores stitch partially-overlapping
    blocks per shard; ``reshard/``). Without it, leaves come back as full
    numpy (the single-process / legacy-compatible path). Reads go through
    :class:`ManifestReader` (mmap-backed zero-copy zip access with a
    per-region cache); pass ``reader`` to reuse one across calls or to
    harvest its exact/assembled counters afterwards.
    """
    import jax.tree_util as jtu

    if reader is None:
        reader = ManifestReader(dirpath)

    paths, t_leaves, treedef = _tree_paths(template)
    _check_unique_paths(paths, "load_sharded")
    if shardings is None:
        s_leaves = [None] * len(t_leaves)
    else:
        s_paths, s_leaves, _ = _tree_paths(shardings)

    restored = []
    for path, tleaf, sleaf in zip(paths, t_leaves, s_leaves):
        meta = reader.leaf_meta(path)
        shape = tuple(meta["shape"])
        if isinstance(sleaf, jax.sharding.Sharding) and shape:
            arr = jax.make_array_from_callback(
                shape, sleaf,
                lambda idx, path=path, shape=shape:
                reader.read_region(
                    path,
                    [sl.start or 0 for sl in idx],
                    [sl.stop if sl.stop is not None else d
                     for sl, d in zip(idx, shape)],
                ),
            )
        else:
            arr = reader.read_region(path, [0] * len(shape), list(shape))
            if not arr.flags.writeable:
                # _RawNpz exact-match views are read-only mmap windows;
                # arrays handed to the caller unsharded must own their
                # memory (and not pin the map open)
                arr = np.array(arr)
        restored.append(arr)
    return jtu.tree_unflatten(treedef, restored)


def peek_leaf(dirpath: str | os.PathLike, leaf_path: str):
    """Read ONE leaf from a sharded checkpoint without a template —
    cheap metadata probes (e.g. which of several checkpoints is newest
    by its ``state/step``). Single-block leaves only (scalars and
    replicated arrays — block 0 carries the whole value)."""
    import json

    dirpath = os.fspath(dirpath)
    with open(os.path.join(dirpath, MANIFEST)) as f:
        manifest = json.load(f)
    meta = manifest["leaves"][leaf_path]
    if len(meta["blocks"]) != 1:
        raise ValueError(
            f"peek_leaf reads single-block leaves; {leaf_path!r} has "
            f"{len(meta['blocks'])} blocks"
        )
    b = meta["blocks"][0]
    npz = np.load(os.path.join(dirpath, b["file"]), allow_pickle=False)
    arr = npz[b["key"]].view(np.dtype(meta["dtype"]))
    return arr.reshape(meta["shape"])


def validate_checkpoint(dirpath: str | os.PathLike) -> list:
    """Problems preventing ``dirpath`` from restoring; ``[]`` means valid.

    The cheap completeness sweep behind fallback restore: manifest parses,
    every referenced shard file exists and opens as a zip (a torn write
    truncates the tail, which holds the zip central directory — so
    truncation fails the open), carries the manifest's save token, and
    contains every block key the manifest assigns to it. Does NOT read
    array payloads — cost is one directory scan plus one tiny member read
    per shard file, safe to run on every resume."""
    import json

    dirpath = os.fspath(dirpath)
    mpath = os.path.join(dirpath, MANIFEST)
    try:
        with open(mpath) as f:
            manifest = json.load(f)
    except FileNotFoundError:
        return [f"no {MANIFEST} (save died before its commit point)"]
    except (OSError, ValueError) as e:
        return [f"unreadable {MANIFEST}: {e}"]

    token = manifest.get("token")
    by_file: dict[str, set] = {}
    for leaf, meta in manifest.get("leaves", {}).items():
        for b in meta.get("blocks", []):
            by_file.setdefault(b["file"], set()).add(b["key"])

    problems = []
    for fname, keys in sorted(by_file.items()):
        fpath = os.path.join(dirpath, fname)
        try:
            with np.load(fpath, allow_pickle=False) as npz:
                members = set(npz.files)
                if token is not None:
                    got = bytes(
                        np.asarray(npz["__token__"]).tobytes()
                    ).hex()
                    if got != token:
                        problems.append(
                            f"{fname}: token {got} != manifest {token} "
                            "(torn save)"
                        )
                        continue
        except FileNotFoundError:
            problems.append(f"{fname}: missing shard file")
            continue
        except Exception as e:
            problems.append(f"{fname}: unreadable ({e})")
            continue
        lost = keys - members
        if lost:
            problems.append(
                f"{fname}: {len(lost)} manifest block(s) absent "
                f"(e.g. {sorted(lost)[0]!r})"
            )
    return problems


STEP_CKPT_RE = re.compile(r"^step-(\d{8,})\.ckpt$")  # 8+: :08d overflows


def legacy_checkpoint_step(path: str | os.PathLike) -> int:
    """``state/step`` of a LEGACY single-file msgpack checkpoint.

    The sharded ranking reads the step with a cheap ``peek_leaf``; the
    legacy format has no manifest, so this restores the msgpack blob and
    digs out ``state/step`` (falling back to the top-level ``step`` the
    payload also carries). Before round 6 the ranking hardcoded legacy
    files to step 0 — a single-file suspend save at step 1000 would LOSE
    resume to a step-100 interval checkpoint (ADVICE r5 #1)."""
    with open(os.fspath(path), "rb") as f:
        sd = serialization.msgpack_restore(f.read())
    node = sd.get("state", {})
    step = node.get("step") if isinstance(node, dict) else None
    if step is None:
        step = sd["step"]  # KeyError → caller logs and discards
    return int(np.asarray(step))


class Checkpointer:
    """latest/best artifact manager for a save directory.

    Sharded saves can run non-blocking: ``save_*_sharded(payload,
    block=False)`` pays only the device→host snapshot on the calling
    thread, writes the token-named shard file on a background thread, and
    defers the commit (cross-host barrier + manifest replace + GC) to
    ``wait()`` — which trainers call at epoch end, on suspend, and before
    any subsequent save, points every rank reaches in the same collective
    order. Until ``wait()`` commits, the previous checkpoint stays fully
    restorable (token-named files are never overwritten). ``save_best``
    fires on metric improvement only, like ``restnet_ddp.py:145-150``.
    """

    def __init__(self, save_dir: str | os.PathLike):
        self.save_dir = os.fspath(save_dir)
        self._thread: Optional[threading.Thread] = None
        self._pending: Optional[_ShardedSave] = None
        self._arena = _Arena()  # snapshot pages reused across saves
        self._warm_thread: Optional[threading.Thread] = None
        self._step_keep: Optional[int] = None  # GC request, runs at wait()

    def _path(self, name: str) -> str:
        return os.path.join(self.save_dir, name)

    @property
    def latest_path(self) -> str:
        return self._path(LATEST)

    @property
    def best_path(self) -> str:
        return self._path(BEST)

    def warm_for(self, payload: Any) -> None:
        """Pre-fault the snapshot arena for ``payload``-sized saves on a
        background thread. Call once at trainer init, after the state is
        built — the page-fault cost (the dominant cost of a first
        snapshot) then overlaps the first compile instead of the first
        best-save. Size is the full local payload footprint — exact for
        single-process runs, an over-estimate (harmless: virtual memory)
        for cross-process-sharded states."""
        def _aligned(nb: int) -> int:
            return -(-nb // 128) * 128  # mirror _ShardedSave's alignment

        nbytes = 0
        for leaf in jax.tree.leaves(payload):
            if (
                isinstance(leaf, jax.Array)
                and leaf.ndim > 0
                and not leaf.is_fully_replicated
            ):
                # sharded branch: one block per canonically-owned shard;
                # addressable shards are an upper bound on ownership
                itemsize = np.dtype(leaf.dtype).itemsize
                for s in leaf.addressable_shards:
                    nbytes += _aligned(
                        int(np.prod(s.data.shape, dtype=np.int64)) * itemsize
                    )
            elif isinstance(leaf, jax.Array):
                # replicated: snapshotted ONCE as a rank-0 block, never
                # once per device copy
                nbytes += _aligned(
                    int(np.prod(leaf.shape, dtype=np.int64))
                    * np.dtype(leaf.dtype).itemsize
                )
            else:
                nbytes += _aligned(np.asarray(leaf).nbytes)
        # the live save payload wraps the state with epoch/step/best
        # scalars the caller doesn't pass here — leave aligned headroom so
        # ensure() never discards the pre-faulted map over a few leaves
        nbytes += 64 * 1024
        tr = spans.tracer()
        here = tr.current()  # the build that asked: the span's cause

        def warm() -> None:
            with tr.span("ckpt.warm_for", cause=here.id if here else None,
                         bytes=nbytes):
                self._arena.warm(nbytes)

        self._warm_thread = threading.Thread(target=warm, daemon=True)
        self._warm_thread.start()

    def has_latest(self) -> bool:
        if os.path.isdir(self.latest_path):
            return self.latest_is_sharded()
        return os.path.exists(self.latest_path)

    def latest_is_sharded(self) -> bool:
        # a dir without a manifest is a save that died before completion —
        # not a restorable checkpoint
        return os.path.isdir(self.latest_path) and os.path.exists(
            os.path.join(self.latest_path, MANIFEST)
        )

    def has_best(self) -> bool:
        if os.path.isdir(self.best_path):
            return self.best_is_sharded()
        return os.path.exists(self.best_path)

    def best_is_sharded(self) -> bool:
        return os.path.isdir(self.best_path) and os.path.exists(
            os.path.join(self.best_path, MANIFEST)
        )

    def _save_sharded(self, path: str, payload: Any, block: bool) -> None:
        self.wait()  # one in-flight save at a time; commit the previous
        if block:
            # blocking: stream from the live buffers — no snapshot copy,
            # no arena (the caller waits, so donation can't race)
            with spans.tracer().span("ckpt.write", blocking=True):
                s = _ShardedSave(path, payload, snapshot=False)
                s.write()
                s.finalize()
        else:
            # snapshot only (fast: bulk copy into the reused arena)
            with spans.tracer().span("ckpt.snapshot"):
                s = _ShardedSave(path, payload, arena=self._arena)
            s.start()  # file write on a thread
            self._pending = s  # commit deferred to wait()

    def save_latest_sharded(self, payload: Any, block: bool = True) -> None:
        """Per-process sharded save of latest (call on ALL processes; see
        ``save_sharded``). The suspend path keeps ``block=True`` — it is
        about to yield, and the commit barrier must run before it does."""
        self._save_sharded(self.latest_path, payload, block)

    def save_best_sharded(self, payload: Any, block: bool = True) -> None:
        self._save_sharded(self.best_path, payload, block)

    # ---- step-interval checkpoints (save_every_n_steps, round 5) ----

    def step_path(self, step: int) -> str:
        return self._path(f"step-{int(step):08d}.ckpt")

    def step_checkpoints(self) -> list:
        """Completed (manifest-bearing) step checkpoints, oldest→newest
        by the step number in the name."""
        out = []
        if not os.path.isdir(self.save_dir):
            return out
        for name in os.listdir(self.save_dir):
            m = STEP_CKPT_RE.match(name)
            p = os.path.join(self.save_dir, name)
            if m and os.path.exists(os.path.join(p, MANIFEST)):
                out.append((int(m.group(1)), p))
        return sorted(out)  # numeric, not lexicographic (9+-digit steps)

    def save_step_sharded(self, payload: Any, step: int,
                          keep_last: int = 3, block: bool = False) -> None:
        """Interval checkpoint ``step-<step>.ckpt`` on the non-stalling
        sharded path (the reference saves only on suspend and on val
        improvement, ``restnet_ddp.py:37-45,145-150`` — a multi-day run
        between val epochs has zero durability; this is the missing
        ``save_every_n_steps`` policy, VERDICT r4 next #6). Retention:
        after this save COMMITS (at ``wait()``), completed step
        checkpoints beyond the newest ``keep_last`` are removed —
        incomplete ones (no manifest) are never counted as kept, and the
        GC runs only after the new save's manifest landed, so it can
        never delete the only complete checkpoint."""
        if keep_last < 1:
            raise ValueError(f"keep_last must be >= 1, got {keep_last}")
        self._save_sharded(self.step_path(step), payload, block)
        self._step_keep = keep_last
        if block:
            self._gc_steps()

    def _gc_steps(self) -> None:
        """Remove completed step checkpoints beyond the newest
        ``_step_keep``, and incomplete step dirs older than the newest
        completed one (debris from crashed saves). Rank 0 only, AFTER the
        commit barrier (shared-fs model, same as the manifest)."""
        import shutil

        keep, self._step_keep = self._step_keep, None
        if keep is None or jax.process_index() != 0:
            return
        done = self.step_checkpoints()
        for _step, path in done[:-keep] if len(done) > keep else []:
            shutil.rmtree(path, ignore_errors=True)
        if done:
            newest_done = done[-1][0]
            for name in os.listdir(self.save_dir):
                m = STEP_CKPT_RE.match(name)
                p = os.path.join(self.save_dir, name)
                if (
                    m and int(m.group(1)) < newest_done
                    and not os.path.exists(os.path.join(p, MANIFEST))
                ):
                    shutil.rmtree(p, ignore_errors=True)

    def restorable_paths(self) -> list:
        """Every VALIDATED restorable checkpoint, newest-first by saved
        ``state/step`` (ties prefer ``latest.ckpt``). Candidates that fail
        :func:`validate_checkpoint` — truncated shard, token mismatch,
        missing blocks — are logged and skipped, so a run whose newest
        save was torn by a crash falls back to the newest *complete* one
        instead of refusing to start (the fallback-restore contract;
        ANALYSIS.md "Failure model & recovery guarantees")."""
        from pytorch_distributed_tpu.utils.logging import rank0_print

        candidates = [p for _s, p in self.step_checkpoints()]
        if self.has_latest():
            candidates.append(self.latest_path)
            if not os.path.isdir(self.latest_path) and len(candidates) > 1:
                rank0_print(
                    f"checkpoint fallback: legacy single-file "
                    f"{self.latest_path} coexists with sharded step "
                    "checkpoints; ranking it by its recorded state/step"
                )
        ranked = []  # (step, tie_rank, path): later candidates win ties
        for rank, p in enumerate(candidates):
            try:
                if os.path.isdir(p):
                    s = int(np.asarray(peek_leaf(p, "state/step")))
                else:
                    # legacy single-file latest: rank by its REAL step
                    # (hardcoding 0 here let an older interval save win
                    # resume over a newer suspend save — ADVICE r5 #1)
                    s = legacy_checkpoint_step(p)
            except Exception as e:
                rank0_print(
                    f"checkpoint fallback: discarding {p} "
                    f"(unreadable step leaf: {e})"
                )
                continue
            ranked.append((s, rank, p))
        out = []
        for s, _rank, p in sorted(ranked, reverse=True):
            if os.path.isdir(p):
                problems = validate_checkpoint(p)
                if problems:
                    rank0_print(
                        f"checkpoint fallback: discarding {p} at step {s}: "
                        + "; ".join(problems)
                    )
                    continue
            out.append(p)
        return out

    def newest_restorable(self) -> Optional[str]:
        """The newest restorable checkpoint that passes validation:
        ``latest.ckpt`` (suspend save) or a step-interval checkpoint,
        whichever carries the highest ``state/step`` — scanning back past
        corrupt candidates (see ``restorable_paths``)."""
        paths = self.restorable_paths()
        return paths[0] if paths else None

    def load_latest_sharded(self, template: Any, shardings: Any = None) -> Any:
        self.wait()
        return load_sharded(self.latest_path, template, shardings)

    def save_latest(self, payload: Any, block: bool = True) -> None:
        if block:
            save_checkpoint(self.latest_path, payload)
            return
        payload = _to_host(payload)  # snapshot before handing to the thread
        self.wait()
        self._thread = threading.Thread(
            target=save_checkpoint, args=(self.latest_path, payload), daemon=True
        )
        self._thread.start()

    def save_best(self, payload: Any) -> None:
        save_checkpoint(self.best_path, payload)

    def load_latest(self, template: Any, shardings: Any = None) -> Any:
        """Same signature as ``load_latest_sharded``/``load_best``: the
        ``shardings`` pytree reaches the sharded reader, so callers get
        placed ``jax.Array`` leaves instead of full-host numpy. (Before
        round 9 this method simply didn't accept the argument — callers
        that passed one to the sibling loaders and then switched to
        ``load_latest`` silently lost their placement and materialized
        the whole state on host.) The legacy single-file branch restores
        host numpy regardless — one msgpack blob has no block table —
        and the caller re-places it (``reshard.load_elastic`` does the
        slice-wise placement when given shardings)."""
        self.wait()
        if self.latest_is_sharded():
            return load_sharded(self.latest_path, template, shardings)
        return load_checkpoint(self.latest_path, template)

    def load_best(self, template: Any, shardings: Any = None) -> Any:
        self.wait()
        if self.best_is_sharded():
            return load_sharded(self.best_path, template, shardings)
        if os.path.isdir(self.best_path):
            raise FileNotFoundError(
                f"{self.best_path} is a directory without a manifest — a "
                "best-save died before its commit point; no completed best "
                "checkpoint exists"
            )
        return load_checkpoint(self.best_path, template)

    def wait(self) -> None:
        """Join any background write and COMMIT any pending sharded save
        (cross-host barrier + manifest + GC). Collective when a sharded
        save is pending multi-process — call at the same point on every
        rank (trainers: epoch end, suspend, before the next save)."""
        if self._warm_thread is not None:
            self._warm_thread.join()  # never race a save into the arena
            self._warm_thread = None
        if self._thread is not None:
            with spans.tracer().span("ckpt.commit_wait"):
                self._thread.join()
            self._thread = None
        if self._pending is not None:
            pending, self._pending = self._pending, None
            with spans.tracer().span("ckpt.commit"):
                pending.finalize()
        self._gc_steps()  # retention only after the new manifest landed

"""Intermediate-activation storage between shards.

The reference stashes each prompt's (prefix, suffix) hidden states between
shard passes in one of three places selected by ``--storage_location``
(``/root/reference/utils.py:159-213``): device memory (``gpu``), host RAM
(``cpu``), or disk ``.npy`` files. This module keeps those three backends —
``tpu`` (HBM), ``cpu`` (host numpy), ``disk`` — with the reference's disk file
naming contract preserved (``suffix{rank}-{idx:05d}.npy`` /
``prefix{rank}-{idx:05d}.npy``, ``/root/reference/utils.py:170-177``) so a
disk-mode run is resumable from the same artifacts.

TPU-first differences:

- Units are *blocks* (a batch of same-bucket prompts = one jitted call), not
  single prompts; disk files are still written per prompt for contract parity.
- No spin-wait backpressure (``sleep(1)`` polls at
  ``/root/reference/utils.py:179-180,189-190``): ordering comes from the
  executor's deterministic schedule. The reference's ``max_activation_in_cpu``
  bound (which *blocks* a producer thread) becomes ``max_in_cpu`` here: once
  that many prompts' activations are resident in host RAM, further blocks
  spill to disk — same bound, no deadlock under a single-driver schedule.
- ``tpu`` keeps activations as device arrays; ``cpu`` uses
  ``jax.device_get`` (async transfer flushed at store time); ``disk`` writes
  float32-preserving raw dtypes via numpy.
- A ``cpu`` store given a ``device_budget`` tiers per block, the way it
  already spills to disk past ``max_in_cpu``: a block whose bytes fit what
  is left of the budget stays a device array (nothing crosses the link),
  one that does not goes to host RAM. ``tpu`` is the same store with no
  bound. The scoring executor derives the budget from the chip where
  nobody set ``--storage_location`` (``residency.activation_budget_bytes``).
- Every ``.npy`` spill carries a checksum sidecar (integrity/manifest.py)
  verified on fetch with a short re-read loop; truncated/undecodable or
  persistently corrupt spills raise typed errors naming the file and shard
  index, and the executor recomputes the block from the last good shard
  boundary (docs/integrity.md) instead of crashing.
"""

from __future__ import annotations

import os

import jax
import numpy as np

import errno

from flexible_llm_sharding_tpu.faults.retry import retry_call
from flexible_llm_sharding_tpu.integrity import manifest as integrity_manifest
from flexible_llm_sharding_tpu.integrity.manifest import (
    SpillCorruptError,
    SpillReadError,
)
from flexible_llm_sharding_tpu.obs import trace as obs_trace
from flexible_llm_sharding_tpu.runtime.pressure import (
    DiskFullError,
    note_event as _note_pressure_event,
)

# Spill-read re-read attempts before a checksum mismatch / decode failure
# is treated as PERSISTENT (and escalated to the executor's recompute
# path): page-cache/NFS corruption heals on a re-read, on-disk corruption
# does not. Cheap — the file is hot in cache after the first attempt.
_SPILL_REREAD_ATTEMPTS = 3


def _save_npy(path: str, arr: np.ndarray) -> None:
    """np.save that round-trips ml_dtypes extension types (bfloat16, fp8):
    the npy format stores them as raw void bytes that np.load returns as
    dtype 'V2', which JAX rejects — so store a same-width uint view instead
    and let :func:`_restore_dtype` restore the real dtype on read. A sidecar
    (``<path>.crc``, integrity/manifest.py) lands atomically alongside so
    every later fetch verifies the bytes it feeds back into the model.

    The write is ATOMIC (temp + rename): ``path`` either holds a complete
    generation or is untouched, and the temp file is removed on any
    failure — a disk-full event (ENOSPC surfaces at flush/close) can
    never leave a truncated spill that later trips integrity re-reads or
    masquerades as on-disk rot. np.save is handed the open file object
    because the path form appends ``.npy`` to names that lack it, which
    would break the temp-name contract."""
    if arr.dtype.isbuiltin == 0:  # extension dtype numpy can't describe
        arr = arr.view(np.dtype(f"u{arr.dtype.itemsize}"))
    tmp = f"{path}.tmp"
    try:
        with open(tmp, "wb") as f:
            np.save(f, arr)
            f.flush()
            os.fsync(f.fileno())  # ENOSPC must surface HERE, not at rename
        os.replace(tmp, path)
    except BaseException:
        try:
            os.remove(tmp)
        except OSError:
            pass  # never-created / already-renamed temp
        raise
    try:
        integrity_manifest.write_sidecar(path, arr)
    except BaseException:
        # The data landed but its NEW checksum didn't: drop whatever
        # sidecar is present (the previous generation's would report the
        # fresh, complete bytes as corruption) — a missing sidecar reads
        # as unverified-but-intact, and the retrying caller rewrites
        # both. Whole-or-absent stays true for the data file.
        integrity_manifest.remove_sidecar(path)
        raise


def _restore_dtype(arr: np.ndarray, np_dtype: np.dtype | None) -> np.ndarray:
    if (
        np_dtype is not None
        and arr.dtype != np_dtype
        and arr.dtype.kind in "uV"
        and arr.dtype.itemsize == np.dtype(np_dtype).itemsize
    ):
        # uint view written by _save_npy (or a raw-void file from an older
        # run): reinterpret as the executor's compute dtype.
        arr = arr.view(np_dtype)
    return arr


class ActivationStore:
    """Store/fetch (prefix_h, suffix_h) activation pairs keyed by block id.

    prefix_h: [B, Lp, D] or None (after the norm stage);
    suffix_h: [B, S, Ls, D].
    """

    def __init__(
        self,
        location: str = "cpu",
        disk_folder: str = "./temp",
        device_rank: int = 0,
        rank_tag: bool = False,
        max_in_cpu: int | None = None,
        np_dtype: np.dtype | None = None,
        batch: int = 0,
        injector=None,
        integrity=None,
        retry_policy=None,
        retry_recorder=None,
        device_budget: int = 0,
    ):
        # injector: chaos-only FaultInjector (corrupt_activation site fires
        # on every spill read; disk_full inside every retried spill
        # write). integrity: metrics.IntegrityRecorder for
        # detected-corruption / re-read-heal counters (None = dropped).
        # retry_policy/retry_recorder: spill WRITES retry ENOSPC under
        # the same transient-I/O ladder as the weight stream (label
        # 'spill_write'); exhaustion raises a typed DiskFullError with
        # no partial file left behind.
        # np_dtype: the compute dtype of stored activations; needed to
        # restore ml_dtypes extension types (bfloat16) from disk files.
        # batch: the num_batch loop index — scopes disk file names (and the
        # resume marker, via the shared tag) per batch, otherwise batch A's
        # re-run would overwrite the files a crashed batch B resumes from
        # (same 0-based prompt indices, same folder). Batch 0 keeps the
        # reference's exact names.
        # device_budget: bytes of blocks a cpu store may keep on the chip
        # at once (0: none; a tpu store keeps every block).
        if location not in ("tpu", "cpu", "disk"):
            raise ValueError(f"storage_location must be tpu|cpu|disk, got {location!r}")
        self.location = location
        self.disk_folder = disk_folder
        self.np_dtype = None if np_dtype is None else np.dtype(np_dtype)
        # The reference tags disk files with the gpu rank only in DP mode
        # (/root/reference/utils.py:172): rank_tag mirrors that.
        self.tag = (str(device_rank) if rank_tag else "") + (
            f".b{batch}" if batch else ""
        )
        self._mem: dict[object, tuple] = {}
        # Blocks of _mem kept as device arrays (id -> bytes) under the
        # budget: all of a tpu store's, those that fit of a cpu store's.
        self.device_budget = {"tpu": float("inf"), "disk": 0}.get(
            location, device_budget
        )
        self._on_device: dict[object, int] = {}
        self._device_held = 0
        # cpu-mode bound (reference's max_activation_in_cpu backpressure,
        # /root/reference/utils.py:179-180): at most this many prompts' worth
        # of activations stay in host RAM; overflow blocks spill to disk.
        # The reference *blocks* a producer thread; here the schedule is
        # deterministic single-driver, so spilling is the non-deadlocking
        # equivalent of the same bound.
        self.max_in_cpu = max_in_cpu
        self._cpu_prompts = 0
        self._spilled: set[object] = set()
        # cpu-mode async offload: the most recent store keeps its device
        # arrays (host DMA started via copy_to_host_async) and is finalised
        # to numpy one store later — so the driver thread never blocks on a
        # device->host copy in the hot loop (the per-store jax.device_get
        # was the host sync that serialised MP pipeline stages). Depth 1
        # bounds the extra HBM to one block's activations.
        self._pending: list[object] = []
        # Seconds the consumer stood blocked on the device inside this
        # store (_finalize), and the ids its device_wait spans carry: the
        # executor's sweep account reads the one and sets the other. It
        # also reads how the pass's bytes split: link_bytes went to the
        # host or came back from it (or from disk), device_bytes stayed on
        # the chip (counted as stored).
        self.device_wait_s = 0.0
        self.link_bytes = self.device_bytes = 0
        self.trace_ids: dict = {}
        # A looped model's exit state of each block between its shards
        # (block id -> llama.exit_init's tuple of small device arrays, a
        # few KB a block: always on the chip, never spilled, so a resumed
        # disk pass has none; runtime/executor.LoopPlace reads and writes).
        self.exit_state: dict = {}
        self._writer = None  # lazy single-thread pool for async disk writes
        self._write_futs: list = []
        self._store_gen = 0  # disk write/read generations (see set_shard)
        self._fetch_gen = 0
        self._shard_idx = 0  # for spill error messages (set_shard)
        self._injector = injector
        self._integrity = integrity
        self._retry = retry_policy
        self._retry_recorder = retry_recorder
        if location == "disk":
            os.makedirs(disk_folder, exist_ok=True)

    # -- paths (reference naming contract, plus a write-generation tag) ----
    def _paths(self, prompt_idx: int, gen: int = 0) -> tuple[str, str]:
        # gen: disk-mode writes ping-pong between two file generations so a
        # shard/stage never overwrites its own INPUT files mid-run — the
        # property crash resume needs (a killed shard k re-runs from the
        # intact generation (k-1)%2; without this, its partial stores would
        # have destroyed some of shard k-1's outputs in place). Generation 0
        # keeps the reference's exact file names
        # (/root/reference/utils.py:172). Cost: steady-state disk holds TWO
        # generations of activation files (the input generation cannot be
        # reclaimed before the shard completes — that is the safety
        # property) — activations are small next to the weights being
        # streamed (~tens of MB/prompt at 7B vs 13.5 GB of weights), and
        # stale files are simply overwritten by the next same-parity shard.
        g = f".g{gen}" if gen else ""
        return (
            os.path.join(
                self.disk_folder, f"prefix{self.tag}-{prompt_idx:05d}{g}.npy"
            ),
            os.path.join(
                self.disk_folder, f"suffix{self.tag}-{prompt_idx:05d}{g}.npy"
            ),
        )

    def set_shard(self, shard_idx: int) -> None:
        """Disk mode: declare the shard/stage about to run; its stores go to
        generation ``shard_idx % 2`` and its fetches read ``(shard_idx-1) % 2``.
        No-op for tpu/cpu stores (the cpu spill path keeps generation 0 —
        spills live and die within one shard, so there is no overwrite
        hazard and no resume)."""
        self._shard_idx = shard_idx
        if self.location == "disk":
            self._store_gen = shard_idx % 2
            self._fetch_gen = (shard_idx - 1) % 2

    # -- block API ---------------------------------------------------------
    def _write_spill(self, path: str, arr: np.ndarray) -> None:
        """One spill-file write, hardened for disk exhaustion: the atomic
        ``_save_npy`` runs under the retry policy (the chaos ``disk_full``
        site fires inside the retried region, exactly like ``shard_read``
        on the weight path), ENOSPC is reported as a pressure event (the
        brownout ladder frees space by shedding), and exhaustion raises a
        typed :class:`DiskFullError` naming the file — with ``path``
        guaranteed whole-or-absent by the temp+rename write."""

        def attempt() -> None:
            try:
                if self._injector is not None:
                    self._injector.fire("disk_full", detail=path)
                _save_npy(path, arr)
            except OSError as e:
                if e.errno == errno.ENOSPC:
                    _note_pressure_event("disk_full")
                raise

        try:
            retry_call(
                attempt,
                policy=self._retry,
                label="spill_write",
                recorder=self._retry_recorder,
            )
        except OSError as e:
            if e.errno == errno.ENOSPC:
                raise DiskFullError(
                    errno.ENOSPC,
                    f"spill write failed, disk full: {path} "
                    f"(shard {self._shard_idx}); no partial file was left",
                ) from e
            raise

    def _store_disk(
        self, prompt_idxs: list[int], prefix_h, suffix_h, gen: int = 0
    ) -> None:
        os.makedirs(self.disk_folder, exist_ok=True)
        prefix_np = None if prefix_h is None else np.asarray(jax.device_get(prefix_h))
        suffix_np = np.asarray(jax.device_get(suffix_h))
        for row, idx in enumerate(prompt_idxs):
            ppath, spath = self._paths(idx, gen)
            self._write_spill(spath, suffix_np[row])
            if prefix_np is not None:
                self._write_spill(ppath, prefix_np[row])

    def _read_spill(self, path: str) -> np.ndarray:
        """One verified spill read: np.load + (chaos) corruption injection
        + sidecar checksum, with up to ``_SPILL_REREAD_ATTEMPTS`` re-reads —
        a re-read heals page-cache/NFS corruption exactly as on the weight
        path. Persistent failure raises ``SpillCorruptError`` (checksum) or
        ``SpillReadError`` (truncated/undecodable), both naming the file
        AND the shard index — never a bare numpy ValueError."""
        where = f"{path} (activation spill, shard {self._shard_idx})"
        last: Exception | None = None
        decode_failure = False
        for attempt in range(_SPILL_REREAD_ATTEMPTS):
            try:
                arr = np.load(path)
                if self._injector is not None:
                    arr = self._injector.corrupt_array(
                        "corrupt_activation", arr, detail=path
                    )
            except (OSError, ValueError, EOFError) as e:
                # Truncated/undecodable .npy (a spill writer killed
                # mid-write, a short read) — retry too: an INJECTED
                # truncated read is transient by construction, and a real
                # short read can be as well.
                last, decode_failure = e, True
                if self._integrity is not None:
                    self._integrity.count("integrity_failures")
                continue
            side = integrity_manifest.read_sidecar(path)
            if side is not None:
                csum, nbytes = side
                if (
                    int(arr.nbytes) != nbytes
                    or integrity_manifest.tensor_checksum(arr) != csum
                ):
                    last, decode_failure = (
                        SpillCorruptError(f"{where}: checksum mismatch"),
                        False,
                    )
                    if self._integrity is not None:
                        self._integrity.count("integrity_failures")
                    continue
            if attempt and self._integrity is not None:
                self._integrity.count("reread_heals")
            return _restore_dtype(arr, self.np_dtype)
        exc_type = SpillReadError if decode_failure else SpillCorruptError
        raise exc_type(
            f"{where}: {'unreadable' if decode_failure else 'corrupt'} after "
            f"{_SPILL_REREAD_ATTEMPTS} read attempt(s): {last!r}"
        ) from last

    def _fetch_disk(self, prompt_idxs: list[int], with_prefix: bool, gen: int = 0):
        prefixes, suffixes = [], []
        for idx in prompt_idxs:
            ppath, spath = self._paths(idx, gen)
            suffixes.append(self._read_spill(spath))
            if with_prefix:
                prefixes.append(self._read_spill(ppath))
        suffix = np.stack(suffixes)
        prefix = np.stack(prefixes) if with_prefix else None
        return prefix, suffix

    def store(self, block_id, prompt_idxs: list[int], prefix_h, suffix_h) -> None:
        if block_id in self._on_device:  # a re-store is decided anew
            self._device_held -= self._on_device.pop(block_id)
            del self._mem[block_id]
        arrays = [a for a in (prefix_h, suffix_h) if a is not None]
        nbytes = sum(a.nbytes for a in arrays)
        # On the chip: what fits the budget's rest, of a tpu store whatever
        # it is handed, of a cpu store the device arrays of a block it holds
        # nowhere else (a block in host RAM or on disk keeps its slot there).
        if self._device_held + nbytes <= self.device_budget and (
            self.location == "tpu"
            or (
                block_id not in self._mem
                and block_id not in self._spilled
                and all(isinstance(a, jax.Array) for a in arrays)
            )
        ):
            self._on_device[block_id] = nbytes
            self._device_held += nbytes
            self._mem[block_id] = (prefix_h, suffix_h)
            self.device_bytes += nbytes
            return
        self.link_bytes += nbytes
        if self.location == "cpu":
            if block_id in self._spilled:
                # A re-store of a currently-spilled block supersedes the disk
                # copy; drop it so fetch() can't return stale data.
                self._spilled.discard(block_id)
                for idx in prompt_idxs:
                    for path in self._paths(idx):
                        try:
                            os.remove(path)
                        except OSError:
                            pass
                        integrity_manifest.remove_sidecar(path)
            over = (
                self.max_in_cpu is not None
                and self._cpu_prompts + len(prompt_idxs) > self.max_in_cpu
                and block_id not in self._mem  # re-stores keep their slot
            )
            if over:
                self._spilled.add(block_id)
                self._submit_disk(prompt_idxs, prefix_h, suffix_h)
                return
            if block_id not in self._mem:
                self._cpu_prompts += len(prompt_idxs)
            for a in (prefix_h, suffix_h):
                if hasattr(a, "copy_to_host_async"):
                    a.copy_to_host_async()
            self._mem[block_id] = (prefix_h, suffix_h)
            if block_id not in self._pending:
                self._pending.append(block_id)
            while len(self._pending) > 1:
                self._finalize(self._pending.pop(0))
        else:  # disk — one file pair per prompt, reference contract
            self._submit_disk(prompt_idxs, prefix_h, suffix_h)

    # -- async disk writer -------------------------------------------------
    # A synchronous _store_disk blocks the driver thread on a device->host
    # copy plus one file write per prompt, serializing device compute with
    # file I/O every block (the reference has the same serialization,
    # /root/reference/utils.py:170-177). A single writer thread overlaps
    # them; the device arrays it holds are exclusively its own (disk-mode
    # fetches re-upload from files, so nothing donates these buffers), and
    # depth is bounded so pending writes can't grow HBM without limit.

    _MAX_PENDING_WRITES = 2

    def _submit_disk(self, prompt_idxs, prefix_h, suffix_h) -> None:
        for a in (prefix_h, suffix_h):
            if hasattr(a, "copy_to_host_async"):
                a.copy_to_host_async()  # start the DMA before queueing
        if self._writer is None:
            from concurrent.futures import ThreadPoolExecutor

            self._writer = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="act-disk-writer"
            )
        self._write_futs.append(
            self._writer.submit(
                self._store_disk,
                prompt_idxs,
                prefix_h,
                suffix_h,
                # Captured NOW: the writer may run after set_shard advances.
                self._store_gen,
            )
        )
        while len(self._write_futs) > self._MAX_PENDING_WRITES:
            self._write_futs.pop(0).result()

    def flush(self) -> None:
        """Barrier: every queued disk write is durably on disk (re-raising
        the first writer failure). The executor calls this before advancing
        a resume progress marker — a marker must never claim a shard whose
        activation files are still in flight."""
        while self._write_futs:
            self._write_futs.pop(0).result()

    def _finalize(self, block_id) -> None:
        """Resolve a cpu-mode block's pending async copy to host numpy,
        releasing its device buffers."""
        if block_id in self._mem:
            p, s = self._mem[block_id]
            # The wait for the block's compute (which waits for its
            # shard's upload), told apart from the copy that follows it.
            with obs_trace.timed(
                "device_wait", cat="sweep", at="act_store", **self.trace_ids
            ) as wait:
                jax.block_until_ready((p, s))
            self.device_wait_s += wait.dur_s
            self._mem[block_id] = (
                None if p is None else np.asarray(p),
                np.asarray(s),
            )

    def fetch(self, block_id, prompt_idxs: list[int], with_prefix: bool = True):
        """Returns (prefix_h | None, suffix_h) as host or device arrays; the
        executor device_puts them as part of the next shard's input feed (a
        block kept on the chip comes back as it was stored, and that
        device_put moves nothing).

        Disk reads flush the async writer first (the queued write may be this
        very block's files); in-memory cpu/tpu fetches don't wait on
        unrelated spill I/O."""
        if block_id in self._on_device:
            self._device_held -= self._on_device.pop(block_id)
            prefix, suffix = self._mem.pop(block_id)
            return (prefix if with_prefix else None), suffix
        prefix, suffix = self._fetch_host(block_id, prompt_idxs, with_prefix)
        self.link_bytes += sum(
            a.nbytes for a in (prefix, suffix) if isinstance(a, np.ndarray)
        )
        return prefix, suffix

    def _fetch_host(self, block_id, prompt_idxs: list[int], with_prefix: bool):
        if self.location == "cpu" and block_id in self._pending:
            self._pending.remove(block_id)
            self._finalize(block_id)
        if self.location == "cpu" and block_id in self._spilled:
            self._spilled.discard(block_id)
            self.flush()
            return self._fetch_disk(prompt_idxs, with_prefix)
        if self.location != "disk":  # tpu: a block it never held is a KeyError
            prefix, suffix = self._mem.pop(block_id)
            if self.location == "cpu":
                self._cpu_prompts -= len(prompt_idxs)
            if not with_prefix:
                prefix = None
            return prefix, suffix
        if self._write_futs:
            self.flush()
        return self._fetch_disk(prompt_idxs, with_prefix, self._fetch_gen)

    def fetch_recompute(
        self, block_id, prompt_idxs: list[int], with_prefix: bool = True
    ):
        """The PREVIOUS shard's inputs for one block (disk mode only): the
        executor's corruption-recompute path re-runs shard k-1 when shard
        k's fetch failed verification. Shard k-1's inputs live at
        generation k%2 == the current STORE generation — untouched for this
        block, because a block's store happens only after its fetch (the
        same ping-pong invariant that protects crash resume)."""
        if self.location != "disk":
            raise SpillCorruptError(
                "recompute needs disk-mode activation generations "
                f"(storage_location={self.location!r} pops its inputs on "
                "fetch)"
            )
        self.flush()
        return self._fetch_disk(prompt_idxs, with_prefix, self._store_gen)

    def clear(self) -> None:
        try:
            if self._write_futs:
                self.flush()
        finally:
            # Shut the writer down even when a flush re-raises a failed
            # write — a leaked pool would pin its queued device arrays.
            if self._writer is not None:
                self._writer.shutdown(wait=True)
                self._writer = None
            self._write_futs.clear()
            self._mem.clear()
            self._on_device.clear()
            self._device_held = 0
            self._spilled.clear()
            self._pending.clear()
            self._cpu_prompts = 0
            self.exit_state.clear()


__all__ = ["ActivationStore"]

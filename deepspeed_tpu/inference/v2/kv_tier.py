"""Tiered KV memory: host-RAM/disk spillover for the prefix cache.

HBM is the hard ceiling on serving scale: a production fleet's system
prompts do not fit in device KV, so before this module a cold prefix
block was simply *dropped* on LRU eviction (``DSStateManager._evict``)
and had to be re-prefilled from scratch on the next match. ZeRO-Infinity
and ZeRO-Offload (PAPERS.md: arxiv 2104.07857, 2101.06840) showed that a
slower-but-larger memory tier with overlapped async transfers turns a
capacity wall into a bandwidth problem; this module applies that
treatment to the prefix cache (docs/SERVING.md "KV tiering"):

- **Spill on eviction.** When the prefix cache evicts a cold indexed
  block, its pool slab bytes (K and V ``[L, KH, bs, D]``, plus the
  ``k_scale``/``v_scale`` plane entries ``[L, KH]`` under kv_quant — so
  the spill rides the int8 4x compression) are copied device→host into
  a bounded host-RAM tier, keyed by the block's original
  ``(parent_hash, tokens)`` index key. Only unreferenced *full* blocks
  are ever evicted, so only those are ever spilled — a referenced or
  partial block can never land in the tier.
- **Demote to disk.** When the host tier exceeds its byte bound, LRU
  entries demote to an optional disk tier through
  ``runtime/swap_tensor`` :class:`AsyncTensorSwapper` (one file per
  entry, CRC-checked — a corrupt or torn file reads back as a *miss*,
  never a crash). Past the disk bound, LRU entries are dropped for
  real.
- **Restore on match.** ``match_prefix`` consults the tier when the
  device index misses: a tier hit allocates a fresh block, starts the
  host→device scatter (dispatched asynchronously — JAX's async dispatch
  returns immediately and the forward that eventually consumes the pool
  orders itself after the copy, so the restore overlaps other
  requests' work instead of blocking the ragged batch), and re-enters
  the block in the index under its original key. The scheduler then
  prefills only the still-cold tail, exactly as for a device hit.

The tier is keyed by content (the index key), not by sequence — two
requests sharing a spilled prefix share the one restored block, and all
refcount/hash-chain invariants of ``ragged/manager.py`` are preserved.
Disabled (the default) the module is never constructed: the eviction
and match paths are byte-for-byte the historical prefix cache.
"""

from __future__ import annotations

import itertools
import os
import zlib
from collections import OrderedDict
from typing import Dict, List, Optional, Tuple

import numpy as np

from ...utils.logging import logger

#: per-process store counter: disk files are namespaced
#: ``kvtier_<pid>_<store>_<n>.swp`` so replicas sharing one ``disk_path``
#: (the frontend applies a single config to every replica engine) can
#: never overwrite or delete each other's entries
_STORE_IDS = itertools.count()

#: stat keys every ``TieredKVStore.stats`` dict carries (and the zeroed
#: shape ``DSStateManager.tier_stats()`` reports with no tier built)
TIER_STAT_KEYS = ("spilled", "restored", "dropped", "demoted",
                  "hits", "misses", "corrupt")
#: occupancy keys (also surfaced through ``DSStateManager.occupancy()``
#: as ``kv_blocks_host_tier`` etc. — the serving gauges read
#: those)
TIER_OCC_KEYS = ("host_blocks", "host_bytes", "disk_blocks", "disk_bytes")


def empty_tier_stats() -> Dict[str, int]:
    """The all-zero stats+occupancy dict a tier-less manager reports —
    one shape for consumers (the replica's delta publish)
    whether or not a tier exists."""
    out = {k: 0 for k in TIER_STAT_KEYS}
    out.update({k: 0 for k in TIER_OCC_KEYS})
    return out


class TieredKVStore:
    """Bounded host-RAM (and optional disk) store of spilled KV blocks.

    Entries are ``{slab_name: np.ndarray}`` dicts — one per-block slab
    per pool tensor (``k``/``v`` and, under kv_quant, the
    ``k_scale``/``v_scale`` plane rows) — keyed by the prefix-cache
    index key. Both tiers are LRU OrderedDicts bounded in *bytes*:
    host overflow demotes to disk (when configured), disk overflow
    drops. ``get`` pops (the device pool becomes the authority again;
    re-eviction re-spills), serving host hits from memory and disk hits
    through :class:`AsyncTensorSwapper` with a CRC integrity check —
    a corrupt entry is counted and treated as a miss.
    """

    def __init__(self, host_max_bytes: int,
                 disk_path: Optional[str] = None,
                 disk_max_bytes: int = 0):
        self.host_max_bytes = int(host_max_bytes)
        self.disk_max_bytes = int(disk_max_bytes)
        self._host: "OrderedDict[tuple, dict]" = OrderedDict()
        self._disk: "OrderedDict[tuple, dict]" = OrderedDict()
        self.host_bytes = 0
        self.disk_bytes = 0
        self._swapper = None
        self._disk_dir = None
        if disk_path and self.disk_max_bytes > 0:
            from ...runtime.swap_tensor.async_swapper import AsyncTensorSwapper

            self._swapper = AsyncTensorSwapper(disk_path)
            self._disk_dir = disk_path
        self._file_prefix = f"kvtier_{os.getpid()}_{next(_STORE_IDS)}"
        self._next_file = 0
        self.stats: Dict[str, int] = {k: 0 for k in TIER_STAT_KEYS}
        if self._disk_dir is not None:
            self._sweep_stale_files()

    def _sweep_stale_files(self) -> None:
        """Remove spill files whose owning PROCESS is gone — a crashed
        or restarted server must not grow a shared ``disk_path`` without
        bound (``disk_max_bytes`` only bounds the live store). Files of
        live processes — sibling replicas in this process included — are
        left strictly alone; when liveness can't be determined the file
        stays (leak-on-doubt beats deleting a live replica's entry)."""
        try:
            names = os.listdir(self._disk_dir)
        except OSError:
            return
        for f in names:
            if not (f.startswith("kvtier_") and f.endswith(".swp")):
                continue
            try:
                pid = int(f.split("_")[1])
            except (IndexError, ValueError):
                continue
            if pid == os.getpid():
                continue                    # this process: maybe live
            try:
                os.kill(pid, 0)
                continue                    # owner alive: not ours to touch
            except ProcessLookupError:
                pass                        # owner dead: stale
            except OSError:
                continue                    # can't tell: leave it
            try:
                os.remove(os.path.join(self._disk_dir, f))
            except OSError:
                pass

    def __del__(self):
        # a replaced engine's store (supervisor restart path) must not
        # orphan its spill files until process exit
        try:
            self.close()
        except Exception:
            pass

    # ------------------------------------------------------------ occupancy
    def occupancy(self) -> Dict[str, int]:
        return {"host_blocks": len(self._host),
                "host_bytes": int(self.host_bytes),
                "disk_blocks": len(self._disk),
                "disk_bytes": int(self.disk_bytes)}

    def __len__(self) -> int:
        return len(self._host) + len(self._disk)

    def __contains__(self, key) -> bool:
        return key in self._host or key in self._disk

    # --------------------------------------------------------------- spill
    def put(self, key: tuple, slabs: Dict[str, np.ndarray], *,
            _count_spill: bool = True) -> bool:
        """Admit one evicted block's slabs under its index key.

        Overwrites any prior entry for the key (same content by
        construction — the key hashes the block's token chain). Returns
        False (counted ``dropped``) when the entry cannot fit the host
        bound at all; otherwise True, demoting/dropping LRU entries as
        the byte bounds require. ``_count_spill=False`` is the
        :meth:`readmit` path — the published counters must stay
        monotonic, so a re-insert never increments-then-decrements."""
        entry = {name: np.ascontiguousarray(a) for name, a in slabs.items()}
        nbytes = sum(a.nbytes for a in entry.values())
        if nbytes > self.host_max_bytes:
            # an entry the host tier can never hold goes STRAIGHT to the
            # disk tier when one exists (a tiny host_max_bytes with a
            # large disk bound is the disk-heavy configuration, not a
            # mistake to silently drop on)
            self._forget(key)
            if self._swapper is not None and self._demote(
                    key, {"slabs": entry, "nbytes": nbytes}):
                if _count_spill:
                    self.stats["spilled"] += 1
                return True
            self.stats["dropped"] += 1
            return False
        self._forget(key)
        self._host[key] = {"slabs": entry, "nbytes": nbytes}
        self.host_bytes += nbytes
        if _count_spill:
            self.stats["spilled"] += 1
        while self.host_bytes > self.host_max_bytes:
            old_key, old = self._host.popitem(last=False)
            self.host_bytes -= old["nbytes"]
            if not self._demote(old_key, old):
                self.stats["dropped"] += 1
        return True

    def _forget(self, key: tuple) -> None:
        """Remove any existing entry for ``key`` from both tiers
        (overwrite path; not a drop — the caller re-inserts)."""
        old = self._host.pop(key, None)
        if old is not None:
            self.host_bytes -= old["nbytes"]
        meta = self._disk.pop(key, None)
        if meta is not None:
            self.disk_bytes -= meta["nbytes"]
            self._remove_file(meta["fkey"])

    # -------------------------------------------------------------- demote
    def _demote(self, key: tuple, entry: dict) -> bool:
        """Move one host entry to the disk tier; False = no disk tier or
        the write failed (the caller counts the block dropped)."""
        if self._swapper is None:
            return False
        names = sorted(entry["slabs"])
        parts = [entry["slabs"][n] for n in names]
        buf = np.concatenate([p.reshape(-1).view(np.uint8) for p in parts])
        fkey = f"{self._file_prefix}_{self._next_file}"
        self._next_file += 1
        try:
            self._swapper.swap_out(fkey, buf)
            self._swapper.wait()
        except Exception as e:
            logger.warning(f"KV tier: disk demotion failed ({e!r}); "
                           "dropping the block")
            # a dispatched-then-failed write may have left a partial
            # file at the final path — it is outside disk_bytes
            # accounting and a live process's sweep never touches it
            self._remove_file(fkey)
            return False
        self._disk[key] = {
            "fkey": fkey, "nbytes": buf.nbytes, "crc": zlib.crc32(buf),
            "parts": [(n, tuple(p.shape), str(p.dtype), p.nbytes)
                      for n, p in zip(names, parts)]}
        self.disk_bytes += buf.nbytes
        self.stats["demoted"] += 1
        while self.disk_bytes > self.disk_max_bytes:
            k2, m2 = self._disk.popitem(last=False)
            self.disk_bytes -= m2["nbytes"]
            self._remove_file(m2["fkey"])
            self.stats["dropped"] += 1
        return True

    def _remove_file(self, fkey: str) -> None:
        if self._disk_dir is None:
            return
        try:
            os.remove(os.path.join(self._disk_dir, f"{fkey}.swp"))
        except OSError:
            pass

    # -------------------------------------------------------------- restore
    def get(self, key: tuple) -> Optional[Dict[str, np.ndarray]]:
        """Pop one entry's slabs (host first, then disk). None = miss —
        including a disk entry whose file is torn, truncated, or fails
        its CRC (counted ``corrupt``): corruption degrades to a
        re-prefill, never an exception on the serving path."""
        entry = self._host.pop(key, None)
        if entry is not None:
            self.host_bytes -= entry["nbytes"]
            self.stats["hits"] += 1
            return entry["slabs"]
        meta = self._disk.pop(key, None)
        if meta is None:
            self.stats["misses"] += 1
            return None
        self.disk_bytes -= meta["nbytes"]
        buf = np.empty(meta["nbytes"], np.uint8)
        try:
            self._swapper.swap_in(meta["fkey"], buf)
            self._swapper.wait()
        except Exception as e:
            logger.warning(f"KV tier: disk read for spilled block failed "
                           f"({e!r}); treating as a miss")
            self.stats["corrupt"] += 1
            self._remove_file(meta["fkey"])
            return None
        if zlib.crc32(buf) != meta["crc"]:
            logger.warning("KV tier: spilled block failed its CRC check; "
                           "treating as a miss")
            self.stats["corrupt"] += 1
            self._remove_file(meta["fkey"])
            return None
        slabs: Dict[str, np.ndarray] = {}
        off = 0
        for name, shape, dt, nb in meta["parts"]:
            slabs[name] = buf[off:off + nb].view(np.dtype(dt)).reshape(shape)
            off += nb
        self.stats["hits"] += 1
        self._remove_file(meta["fkey"])
        return slabs

    def discard(self, key: tuple) -> None:
        """Drop an entry from both tiers (and its disk file) WITHOUT
        touching the hit/miss counters — the cancel path for parked
        preemption payloads, not a serving-path lookup."""
        self._forget(key)

    def readmit(self, key: tuple, slabs: Dict[str, np.ndarray]) -> None:
        """Put back an entry whose restore failed (no device block could
        be freed): the ``get`` that fetched it was not a real hit — the
        match degraded to a miss — and the re-insert is not a new spill
        (``_count_spill=False``: the ``spilled`` counter other threads
        sample for delta/reset math must never dip, or a transient read
        would masquerade as an engine swap). Keeps hit/miss/spill
        describing what the serving path actually experienced, so a
        pool wedged by live sequences can't report a 100%-hit tier."""
        self.stats["hits"] -= 1
        self.stats["misses"] += 1
        self.put(key, slabs, _count_spill=False)

    # ------------------------------------------------------------ lifecycle
    def lru_keys(self) -> Tuple[List[tuple], List[tuple]]:
        """(host keys, disk keys) oldest-first — test/introspection
        surface for the LRU ordering invariant."""
        return list(self._host), list(self._disk)

    def clear(self) -> None:
        for meta in self._disk.values():
            self._remove_file(meta["fkey"])
        self._host.clear()
        self._disk.clear()
        self.host_bytes = 0
        self.disk_bytes = 0

    def close(self) -> None:
        self.clear()
        if self._swapper is not None:
            self._swapper.close()
            self._swapper = None

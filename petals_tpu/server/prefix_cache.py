"""Cross-session radix prefix tree: identical prompt prefixes across sessions
skip their prefill compute (beats the reference, which recomputes every
session's full prompt; the vLLM/SGLang automatic-prefix-caching idea, built
for this server's hidden-state wire protocol).

Servers receive prefills as HIDDEN STATES, which are deterministic functions
of the prompt prefix for a fixed model/span — so a prefix is identified by a
hash CHAIN over fixed-size token segments: key_i = H(key_{i-1}, bytes of
segment i). Because every key commits to its whole ancestry, the chain IS a
radix tree: two prompts that share j segments share exactly keys[0..j), and
the store's per-key nodes link parent -> children along the chains they were
stored under. A session's prefill probes its chain for the longest cached
path, seeds its KV buffers, computes only the tail, and stores the new
segments as a fresh branch. Rollbacks can't poison the store: nodes are
content-addressed (same segment bytes -> same KV), never keyed by session
state.

Every node carries one of three residency states:

- **HBM** — the node's k/v additionally live on device, either as pinned
  copy-on-write page runs in the batcher's paged pool (a pooled hit adopts
  them by block-table reference: zero bytes copied) or as device-array
  slices (``kd``/``vd``); a whole-path HBM hit seeds the session without any
  host->device transfer.
- **host** — numpy k/v/out in the cache's own byte budget (``max_bytes``);
  a hit re-uploads through the staging path.
- **swapped** — the arrays' bytes are charged to the PR-4 ``HostSwapPool``
  (the same budget session preemption swaps into) instead of the cache
  budget; a hit promotes the node back to the host tier through the same
  accounting, evicting colder nodes to make room.

Eviction walks leaf-first down the tiers — device refs drop before host
bytes, host bytes demote to swap before nodes are removed outright — and
victims are ranked by the prefix-cache economics counters (per-node hit
count, recency) *after* the owning tenant's ledger share: the node of the
peer with the highest dominant-resource share (``usage_fn``, the PR-10
DRF rank) goes first, so one tenant's cold subtree can never squat in HBM
past its fair share while other tenants churn. Interior nodes are never
removed while a descendant survives (probes walk keys in order; removing an
ancestor would orphan the whole subtree) — they demote to swap instead,
which keeps the path probe-able. Per-tenant resident bytes are billed to
the ResourceLedger as a piecewise-constant cache-residency rate
(``set_cache_rates``), so /ledger shows who the cache is spending its
budget on.

Trust model (standard automatic-prefix-caching tradeoff): the cache is
shared across ALL clients of this server by default, and a hit is faster
than a miss in a way a client can time — so any client that can produce the
same hidden states (i.e. knows the model and a candidate prompt) can probe
whether that prompt prefix was recently served to someone else. In an open
swarm this is consistent with the existing trust model: prompt hidden
states already transit servers in the clear, so a server (or anyone who can
hash candidate prompts) learns nothing new from the cache — only OTHER
clients gain the timing probe. Deployments that care can set the handler's
``prefix_share_scope="peer"``, which folds the requesting peer's id into
the hash salt: each client then only ever hits its own entries, closing the
cross-tenant channel at the cost of cross-client sharing.
"""

from __future__ import annotations

import hashlib
import os
import threading
from collections import OrderedDict
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from petals_tpu.telemetry import instruments as tm
from petals_tpu.utils.logging import get_logger

logger = get_logger(__name__)

SEGMENT_TOKENS = 128

# device-tier promotion threshold: a host-resident node must be hit this many
# times before maybe_promote_device uploads it (a one-off hit does not pay
# for an HBM slot; the second hit predicts a third). Env-tunable so revival
# step 10/10 can retune the silicon crossover without code edits.
PROMOTE_MIN_HITS = int(os.environ.get("PETALS_TPU_PROMOTE_MIN_HITS", "2"))


def resolve_device_bytes(prefix_cache_bytes: int, prefix_device_bytes: int) -> int:
    """The radix cache's HBM tier size: ``PETALS_TPU_RADIX_DEVICE_FRAC``
    (a fraction of the host budget, clamped to [0, 1]) overrides the
    configured byte count, so operators can retune the device/host split per
    silicon generation from the environment."""
    frac = os.environ.get("PETALS_TPU_RADIX_DEVICE_FRAC")
    if frac is None:
        return prefix_device_bytes
    try:
        f = min(max(float(frac), 0.0), 1.0)
    except ValueError:
        logger.warning(
            f"Ignoring malformed PETALS_TPU_RADIX_DEVICE_FRAC={frac!r}"
        )
        return prefix_device_bytes
    return int(f * max(prefix_cache_bytes, 0))

# the cache may reserve at most this fraction of the HostSwapPool for demoted
# nodes: session preemption and the prefix cache share ONE budget, and a cold
# cache must never make a live session unswappable
CACHE_SWAP_FRAC = 0.5


def segment_keys(hidden: np.ndarray, salt: str) -> List[str]:
    """Hash-chain keys for every FULL segment of ``hidden`` [1, seq, h].
    blake2b (fast, keyed by the span salt so spans never cross-pollute)."""
    seq = hidden.shape[1]
    keys = []
    prev = salt.encode()
    for s in range(seq // SEGMENT_TOKENS):
        seg = np.ascontiguousarray(hidden[:, s * SEGMENT_TOKENS : (s + 1) * SEGMENT_TOKENS])
        h = hashlib.blake2b(prev, digest_size=16)
        h.update(seg.tobytes())
        prev = h.digest()
        keys.append(prev.hex())
    return keys


class RadixPrefixCache:
    """Radix tree of per-segment (k, v, out) nodes with three-tier residency.

    The node store stays an ``OrderedDict`` keyed by chain hash (insertion /
    touch order doubles as the flat-LRU order for ``policy="lru"``); tree
    structure rides on per-node ``parent``/``children`` links derived from
    the chains nodes are stored under. ``policy="radix"`` (the default)
    enables tree-aware eviction, economics scoring, and swap spillover;
    ``policy="lru"`` reproduces the flat byte-budgeted LRU (the A/B baseline
    the bench rows compare against — same budgets, no tree protection).

    The DEVICE tier (``device_max_bytes``) keeps hot nodes' k/v additionally
    resident in HBM: a hit whose whole path is device-resident seeds the
    session without any host->device transfer, which is what makes a prefix
    hit decisively cheaper than the prefill it skips (the only chip record,
    July 2026 and before this tier existed: the host-tier hit's KV re-upload
    cost about as much as the skipped compute, 1.04x TTFT; the crossover is
    not measured on the current chip, ROADMAP S4). Device entries
    are an optimization only: eviction drops the HBM reference, the host
    copy stays, and the seed path falls back to the host staging route."""

    def __init__(
        self,
        max_bytes: int,
        device_max_bytes: int = 0,
        *,
        policy: str = "radix",
        swap_pool=None,  # memory_cache.HostSwapPool (shared with session swap)
        usage_fn: Optional[Callable[[Optional[str]], float]] = None,
        ledger=None,  # telemetry.ledger.ResourceLedger (cache-residency billing)
        swap_frac: float = CACHE_SWAP_FRAC,
    ):
        if policy not in ("radix", "lru"):
            raise ValueError(f"policy must be 'radix' or 'lru', got {policy!r}")
        self.max_bytes = max_bytes
        self.device_max_bytes = device_max_bytes
        self.policy = policy
        self.swap_pool = swap_pool
        self.usage_fn = usage_fn
        self.ledger = ledger
        self.swap_frac = float(swap_frac)
        self._store: "OrderedDict[str, dict]" = OrderedDict()
        self._bytes = 0  # host tier (swapped nodes charge the pool instead)
        self._dev_bytes = 0
        self._swap_bytes = 0  # our share of swap_pool.bytes_in_use
        self._tick = 0  # logical clock for recency scoring
        # all methods may be called from the event loop AND from worker
        # threads (maybe_promote_device runs its uploads off-loop), so every
        # mutation holds the mutex; get_entries returns plain references,
        # which stay valid across a concurrent eviction (dict pops only)
        self._mutex = threading.RLock()
        self.stats = {
            "hits": 0, "misses": 0, "hit_tokens": 0, "stored_segments": 0,
            "evictions": 0, "demotions": 0, "promotions": 0,
            "swap_evictions": 0, "device_evictions": 0,
        }

    @property
    def current_bytes(self) -> int:
        return self._bytes

    @property
    def swap_bytes(self) -> int:
        return self._swap_bytes

    # ------------------------------------------------------------------ probe

    def probe(self, keys: Sequence[str]) -> int:
        """Longest cached path (in segments). Touches every node on the path
        (hit count + recency — the economics counters scoring stays/evicts)
        and promotes swapped nodes back to the host tier so the seed path
        reads them at host cost, evicting colder nodes to make room."""
        with self._mutex:
            self._tick += 1
            n = 0
            path: List[str] = []
            for key in keys:
                entry = self._store.get(key)
                if entry is None:
                    break
                entry["hits"] += 1
                entry["last_use"] = self._tick
                self._store.move_to_end(key)
                path.append(key)
                n += 1
            if n and self.policy == "radix" and self.swap_pool is not None:
                protect = frozenset(keys)
                for key in path:
                    self._promote_host(key, protect)
            if n:
                self.stats["hits"] += 1
                self.stats["hit_tokens"] += n * SEGMENT_TOKENS
                tm.PREFIX_HIT.inc()
            else:
                self.stats["misses"] += 1
                tm.PREFIX_MISS.inc()
            self._bill()
            return n

    def get_entries(self, keys: Sequence[str], n: int) -> List[dict]:
        """Entry references for segments [0, n). Cheap dict lookups — callers
        on the event loop resolve these BEFORE handing the multi-MB
        concatenation to a worker thread: a concurrent put()'s eviction only
        pops dict slots, so already-held references stay valid, whereas
        re-looking keys up from the thread can raise KeyError mid-read."""
        with self._mutex:
            return [self._store[k] for k in keys[:n]]

    @staticmethod
    def concat_entries(entries: Sequence[dict]) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Concatenate resolved entries along the token axis:
        k/v [n_blocks, 1, n*SEG, hkv, d], out [1, n*SEG, hidden]."""
        k = np.concatenate([e["k"] for e in entries], axis=2)
        v = np.concatenate([e["v"] for e in entries], axis=2)
        out = np.concatenate([e["out"] for e in entries], axis=1)
        return k, v, out

    def get_range(self, keys: Sequence[str], n: int) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """get_entries + concat_entries in one call (single-threaded users)."""
        return self.concat_entries(self.get_entries(keys, n))

    # ------------------------------------------------------------------- put

    def put(
        self, keys: Sequence[str], first: int,
        k: np.ndarray, v: np.ndarray, out: np.ndarray,
        k_dev=None, v_dev=None,
        pages: Optional[Sequence[int]] = None, pages_pool=None, pages_epoch: int = 0,
        tenant: Optional[str] = None,
    ) -> None:
        """Store segments [first, len(keys)) from span-shaped arrays COVERING
        those segments: k/v [n_blocks, 1, tokens, hkv, d] and out
        [1, tokens, hidden] whose token axis starts at segment ``first``.
        ``k_dev``/``v_dev``, when given, are the same token range as DEVICE
        arrays; their per-segment slices populate the device tier.

        ``pages``/``pages_pool``/``pages_epoch``: page-granular sharing for a
        paged batcher. ``pages`` are PINNED page indices (pin_lane_pages)
        covering the same token range; each segment's slice rides on its
        entry so a later hit can adopt_pages the prefix with zero copies.
        Ownership transfers to the cache here: every incoming page reference
        is either attached to an entry or unpinned before put returns, and
        attached pins are unpinned on eviction/clear — copy-on-write in the
        batcher keeps pinned pages immutable while referenced.

        ``tenant`` is the storing peer's id: residency is billed to it
        through the ledger, and eviction under pressure takes the dominant
        tenant's nodes first (the DRF victim ordering)."""
        with self._mutex:
            self._put_locked(
                keys, first, k, v, out, k_dev, v_dev,
                pages, pages_pool, pages_epoch, tenant,
            )
            self._bill()

    def _put_locked(
        self, keys, first, k, v, out, k_dev, v_dev,
        pages, pages_pool, pages_epoch, tenant,
    ) -> None:
        self._tick += 1
        spp = 0
        if pages is not None and pages_pool is not None and pages_pool.page_size:
            spp = SEGMENT_TOKENS // pages_pool.page_size  # pages per segment

        def unpin_from(seg: int) -> None:
            if spp and pages[seg * spp:]:
                pages_pool.unpin_pages(pages[seg * spp:], pages_epoch)

        protect = frozenset(keys)
        for i, key in enumerate(keys[first:]):
            t0, t1 = i * SEGMENT_TOKENS, (i + 1) * SEGMENT_TOKENS
            j = first + i  # absolute segment index along the chain
            seg_pages = list(pages[i * spp : (i + 1) * spp]) if spp else None
            if key in self._store:
                entry = self._store[key]
                self._store.move_to_end(key)
                entry["last_use"] = self._tick
                # a re-store is evidence of heat: a swapped node regaining
                # HBM residency (pages / device refs below) must come back
                # to the host tier first — swap never holds device pins
                if entry.get("swapped"):
                    self._promote_host(key, protect)
                # a hot entry first stored host-only (pooled/lockstep store,
                # or after device eviction) gains HBM residency on its next
                # device-capable store — otherwise popular prefixes would be
                # locked out of the tier forever while one-offs fill it
                if not entry.get("swapped"):
                    if t1 <= k.shape[2]:
                        self._attach_device(entry, k_dev, v_dev, t0, t1)
                    if seg_pages and not self._attach_pages(
                        entry, seg_pages, pages_pool, pages_epoch
                    ):
                        pages_pool.unpin_pages(seg_pages, pages_epoch)
                        seg_pages = None
                elif seg_pages:
                    pages_pool.unpin_pages(seg_pages, pages_epoch)
                continue
            if t1 > k.shape[2]:
                unpin_from(i)
                break
            entry = {
                "k": np.ascontiguousarray(k[:, :, t0:t1]),
                "v": np.ascontiguousarray(v[:, :, t0:t1]),
                "out": np.ascontiguousarray(out[:, t0:t1]),
            }
            entry_bytes = sum(a.nbytes for a in entry.values())
            if entry_bytes > self.max_bytes:
                unpin_from(i)
                return  # a single segment over budget: nothing fits
            if not self._make_room(entry_bytes, protect):
                # budget full of hotter/unevictable nodes: stop the whole
                # chain here — storing a deeper segment whose ancestor was
                # refused would leave an unreachable orphan
                unpin_from(i)
                return
            entry["bytes"] = entry_bytes
            parent = keys[j - 1] if j > 0 else None
            parent_entry = self._store.get(parent) if parent is not None else None
            entry["parent"] = parent if parent_entry is not None else None
            entry["children"] = set()
            entry["depth"] = (
                parent_entry["depth"] + 1 if parent_entry is not None else 0
            )
            entry["tenant"] = tenant
            entry["hits"] = 0
            entry["last_use"] = self._tick
            entry["swapped"] = False
            if parent_entry is not None:
                parent_entry["children"].add(key)
            self._attach_device(entry, k_dev, v_dev, t0, t1)
            if seg_pages:
                self._attach_pages(entry, seg_pages, pages_pool, pages_epoch)
            self._store[key] = entry
            self._bytes += entry_bytes
            self.stats["stored_segments"] += 1

    # -------------------------------------------------------------- residency

    def _tenant_share(self, shares: Dict, tenant: Optional[str]) -> float:
        """Cached dominant-resource share of ``tenant`` (0.0 without a
        usage_fn — victim ordering then falls back to pure economics)."""
        if tenant not in shares:
            share = 0.0
            if self.usage_fn is not None:
                try:
                    share = float(self.usage_fn(tenant))
                except Exception as e:
                    logger.warning(f"prefix-cache usage_fn failed for {tenant!r}: {e}")
            shares[tenant] = share
        return shares[tenant]

    def _host_leaf(self, entry: dict) -> bool:
        """Host-resident with no host-resident child: the bottom of the
        host tier under this node — demotion/eviction works upward from
        these (never strands a hotter descendant below a removed ancestor)."""
        if entry.get("swapped"):
            return False
        for c in entry["children"]:
            ce = self._store.get(c)
            if ce is not None and not ce.get("swapped"):
                return False
        return True

    def _pick_victim(self, protect: frozenset, skip: set) -> Optional[str]:
        """Leaf-first economics victim: among host-tier leaves, the node of
        the most dominant tenant, then fewest hits, then least recent. The
        hit count is the bytes-saved-per-byte-held economics in one number:
        every node is one segment, so hits * SEGMENT_TOKENS of prefill saved
        per entry_bytes held — comparing hit counts compares the ratios."""
        best_key = None
        best_rank = None
        shares: Dict = {}
        for key, entry in self._store.items():
            if key in protect or key in skip:
                continue
            if not self._host_leaf(entry):
                continue
            rank = (
                -self._tenant_share(shares, entry.get("tenant")),
                entry["hits"],
                entry["last_use"],
            )
            if best_rank is None or rank < best_rank:
                best_key, best_rank = key, rank
        return best_key

    def _make_room(self, need: int, protect: frozenset) -> bool:
        """Free host-tier bytes until ``need`` fits. Flat policy evicts in
        store (LRU) order; radix demotes leaf-first into the swap tier and
        only removes nodes outright when they have no surviving descendants
        (or no swap room)."""
        if self._bytes + need <= self.max_bytes:
            return True
        if self.policy != "radix":
            while self._bytes + need > self.max_bytes and self._store:
                self._evict_node(next(iter(self._store)))
            return self._bytes + need <= self.max_bytes
        skip: set = set()
        while self._bytes + need > self.max_bytes:
            victim = self._pick_victim(protect, skip)
            if victim is None:
                return False
            if self._demote_node(victim, protect):
                continue
            entry = self._store[victim]
            if any(c in self._store for c in entry["children"]):
                # interior node (its children are swapped): removal would
                # orphan the subtree, and it can't demote — leave it and
                # look for another victim
                skip.add(victim)
                continue
            self._evict_node(victim)
        return True

    def _demote_node(self, key: str, protect: frozenset) -> bool:
        """host -> swapped: move the node's byte charge from the cache
        budget into the HostSwapPool (the arrays stay where they are — the
        tier is an accounting boundary; what changes is whose budget holds
        the bytes and that the node sheds all HBM residency)."""
        entry = self._store[key]
        if self.swap_pool is None:
            return False
        if not self._swap_reserve(entry["bytes"], protect):
            return False
        self._drop_device(entry)
        self._unpin_entry(entry)
        entry["swapped"] = True
        self._bytes -= entry["bytes"]
        self._swap_bytes += entry["bytes"]
        self.stats["demotions"] += 1
        tm.PREFIX_DEMOTE.inc()
        return True

    def _swap_reserve(self, nbytes: int, protect: frozenset) -> bool:
        """Reserve cache-tagged swap bytes, evicting our own coldest swapped
        nodes to stay under the cache's fraction of the shared budget (the
        session swap path must always find room the cache didn't eat)."""
        cap = int(self.swap_frac * self.swap_pool.max_size_bytes)
        if nbytes > cap:
            return False
        while True:
            # ownership transfer: the reservation belongs to the demoted
            # node; _promote_host / _evict_node free(kind="cache") it
            if self._swap_bytes + nbytes <= cap and self.swap_pool.try_reserve(
                nbytes, kind="cache"
            ):
                return True
            victim = self._pick_swapped_victim(protect)
            if victim is None:
                return False
            self._evict_node(victim)
            self.stats["swap_evictions"] += 1
            tm.PREFIX_SWAP_EVICT.inc()

    def _pick_swapped_victim(self, protect: frozenset) -> Optional[str]:
        """Coldest childless swapped node (swap-tier eviction order).
        ``protect`` covers the chain being probed/stored — a node mid-
        promotion must not be evicted out from under its own promotion."""
        best_key = None
        best_rank = None
        shares: Dict = {}
        for key, entry in self._store.items():
            if not entry.get("swapped") or key in protect:
                continue
            if any(c in self._store for c in entry["children"]):
                continue
            rank = (
                -self._tenant_share(shares, entry.get("tenant")),
                entry["hits"],
                entry["last_use"],
            )
            if best_rank is None or rank < best_rank:
                best_key, best_rank = key, rank
        return best_key

    def _promote_host(self, key: str, protect: frozenset) -> bool:
        """swapped -> host on a hit (the swap-in of the cache plane): free
        the pool reservation and re-charge the cache budget, making room by
        demoting colder nodes. Failure is benign — the node still serves,
        it just keeps charging the swap pool until a later hit succeeds."""
        entry = self._store.get(key)
        if entry is None or not entry.get("swapped"):
            return False
        if not self._make_room(entry["bytes"], protect):
            return False
        self.swap_pool.free(entry["bytes"], kind="cache")
        self._swap_bytes -= entry["bytes"]
        entry["swapped"] = False
        self._bytes += entry["bytes"]
        self.stats["promotions"] += 1
        tm.PREFIX_PROMOTE.inc()
        return True

    def maybe_promote_device(self, keys: Sequence[str], n: int) -> int:
        """host -> HBM for hot hit-path nodes: upload k/v of every node on
        ``keys[:n]`` that has been hit at least PROMOTE_MIN_HITS times and
        lacks device refs. Called by the handler OFF the event loop after a
        host-tier hit (uploads are multi-MB device transfers); by the next
        probe the whole path is device-resident and the session seeds with
        zero host->device traffic. Returns the number promoted."""
        if self.device_max_bytes <= 0 or self.policy != "radix":
            return 0
        import jax.numpy as jnp  # lazy: host-only users never touch jax

        promoted = 0
        for key in list(keys[:n]):
            with self._mutex:
                entry = self._store.get(key)
                if (
                    entry is None
                    or entry.get("swapped")
                    or "kd" in entry
                    or entry["hits"] < PROMOTE_MIN_HITS
                ):
                    continue
                k_host, v_host = entry["k"], entry["v"]
            # the uploads run OUTSIDE the mutex: a concurrent probe must not
            # stall behind a host->device copy
            kd = jnp.asarray(k_host)
            vd = jnp.asarray(v_host)
            with self._mutex:
                entry = self._store.get(key)
                if entry is None or "kd" in entry or entry.get("swapped"):
                    continue
                dev_bytes = int(kd.nbytes) + int(vd.nbytes)
                if dev_bytes > self.device_max_bytes:
                    continue
                self._evict_device(self.device_max_bytes - dev_bytes)
                entry["kd"], entry["vd"] = kd, vd
                entry["dev_bytes"] = dev_bytes
                self._dev_bytes += dev_bytes
                promoted += 1
                self.stats["promotions"] += 1
                tm.PREFIX_PROMOTE.inc()
        if promoted:
            with self._mutex:
                self._bill()
        return promoted

    # ------------------------------------------------------------ device tier

    def _attach_device(self, entry: dict, k_dev, v_dev, t0: int, t1: int) -> None:
        """Pin the [t0, t1) token slice of the device arrays onto ``entry``
        (no-op without device arrays, budget, or when already resident)."""
        if k_dev is None or self.device_max_bytes <= 0 or "kd" in entry:
            return
        kd = k_dev[:, :, t0:t1]
        vd = v_dev[:, :, t0:t1]
        dev_bytes = int(kd.nbytes) + int(vd.nbytes)
        if dev_bytes <= self.device_max_bytes:
            self._evict_device(self.device_max_bytes - dev_bytes)
            entry["kd"], entry["vd"] = kd, vd
            entry["dev_bytes"] = dev_bytes
            self._dev_bytes += dev_bytes

    def _attach_pages(self, entry: dict, seg_pages, pool, epoch: int) -> bool:
        """Attach a pinned page run to ``entry`` (paged tier). Replaces a
        stale-epoch run; returns False when the entry already holds a live
        one (caller unpins the incoming duplicate)."""
        if "pages" in entry:
            if entry.get("pages_epoch") == getattr(pool, "page_epoch", -1):
                return False
            self._unpin_entry(entry)  # stale epoch: pins died with the pool
        entry["pages"] = list(seg_pages)
        entry["pages_pool"] = pool
        entry["pages_epoch"] = epoch
        return True

    def _unpin_entry(self, entry: dict) -> None:
        """Release an entry's page pins back to its batcher (eviction/clear/
        demotion). Best-effort: a reset batcher ignores stale-epoch unpins."""
        pages = entry.pop("pages", None)
        pool = entry.pop("pages_pool", None)
        epoch = entry.pop("pages_epoch", 0)
        if pages and pool is not None:
            try:
                pool.unpin_pages(pages, epoch)
            except Exception:  # swarmlint: disable=no-silent-except — racing batcher close/reset: the pool (and its pins) are gone anyway
                pass

    def _drop_device(self, entry: dict) -> None:
        """Drop one entry's HBM array refs (host copy stays). Counted: the
        device tier's churn was invisible in telemetry before this."""
        dev = entry.pop("dev_bytes", 0)
        if dev:
            entry.pop("kd", None)
            entry.pop("vd", None)
            self._dev_bytes -= dev
            self.stats["device_evictions"] += 1
            tm.PREFIX_DEVICE_EVICT.inc()

    def _evict_device(self, target_bytes: int) -> None:
        """Drop HBM references until the device tier fits ``target_bytes``;
        host copies stay, so this only downgrades hits. Flat policy drops
        oldest-first (store order); radix drops coldest-first (economics)."""
        if self._dev_bytes <= target_bytes:
            return
        entries = list(self._store.values())
        if self.policy == "radix":
            entries.sort(key=lambda e: (e["hits"], e["last_use"]))
        for entry in entries:
            if self._dev_bytes <= target_bytes:
                break
            self._drop_device(entry)

    # -------------------------------------------------------------- eviction

    def _evict_node(self, key: str) -> None:
        """Remove a node outright from whatever tier holds it, releasing its
        HBM pins and its byte charge, and detaching it from the tree."""
        entry = self._store.pop(key)
        self._drop_device(entry)
        self._unpin_entry(entry)
        if entry.get("swapped"):
            self.swap_pool.free(entry["bytes"], kind="cache")
            self._swap_bytes -= entry["bytes"]
        else:
            self._bytes -= entry["bytes"]
        parent = self._store.get(entry.get("parent"))
        if parent is not None:
            parent["children"].discard(key)
        self.stats["evictions"] += 1
        tm.PREFIX_EVICT.inc()

    def clear(self) -> None:
        """Drop every node (stats are kept — they describe the lifetime)."""
        with self._mutex:
            for entry in self._store.values():
                self._unpin_entry(entry)
                if entry.get("swapped") and self.swap_pool is not None:
                    self.swap_pool.free(entry["bytes"], kind="cache")
            self._store.clear()
            self._bytes = 0
            self._dev_bytes = 0
            self._swap_bytes = 0
            self._bill()

    # ------------------------------------------------------------------ views

    def worth_storing(
        self, keys: Sequence[str], first: int, est_entry_bytes: int,
        device_capable: bool = False, pages_pool=None,
    ) -> bool:
        """Whether a store pass would actually add anything (callers use
        this to skip the device->host snapshot entirely otherwise):

        - at least one novel key whose single entry fits the budget; or
        - ``device_capable`` and a host-resident key that lacks device refs
          (a hot entry first stored by a pooled/lockstep path gains HBM
          residency on its next device-capable store — without this check a
          host-resident hot entry reported "nothing to add" and was locked
          out of the tier forever); or
        - ``pages_pool`` given and a key without a live page run in THAT
          pool at its current epoch (pool resets kill pins; the re-store
          re-pins them).
        """
        if est_entry_bytes > self.max_bytes:
            return False
        with self._mutex:
            tail = keys[first:]
            if any(k not in self._store for k in tail):
                return True
            if device_capable and self.device_max_bytes > 0:
                for k in tail:
                    entry = self._store[k]
                    if "kd" not in entry and not entry.get("swapped"):
                        return True
            if pages_pool is not None and getattr(pages_pool, "page_size", None):
                epoch = getattr(pages_pool, "page_epoch", -1)
                for k in tail:
                    entry = self._store[k]
                    if entry.get("swapped"):
                        continue
                    if (
                        entry.get("pages") is None
                        or entry.get("pages_pool") is not pages_pool
                        or entry.get("pages_epoch") != epoch
                    ):
                        return True
            return False

    def _bill(self) -> None:
        """Push per-tenant resident bytes (host + device + swap + pinned
        pages) to the ledger as the new piecewise-constant cache-residency
        rate. Called (under the mutex) at the end of every mutating op."""
        if self.ledger is None:
            return
        by_tenant: Dict[Optional[str], float] = {}
        for entry in self._store.values():
            nbytes = entry["bytes"] + entry.get("dev_bytes", 0)
            pages = entry.get("pages")
            if pages:
                nbytes += len(pages) * int(
                    getattr(entry.get("pages_pool"), "page_nbytes", 0) or 0
                )
            tenant = entry.get("tenant")
            by_tenant[tenant] = by_tenant.get(tenant, 0.0) + nbytes
        try:
            self.ledger.set_cache_rates(by_tenant)
        except Exception as e:
            logger.warning(f"prefix-cache ledger billing failed: {e}")

    def summary(self) -> dict:
        with self._mutex:
            page_bytes = 0
            swapped = 0
            max_depth = 0
            for e in self._store.values():
                if e.get("swapped"):
                    swapped += 1
                pages = e.get("pages")
                if pages:
                    page_bytes += len(pages) * int(
                        getattr(e.get("pages_pool"), "page_nbytes", 0) or 0
                    )
                max_depth = max(max_depth, e.get("depth", 0))
            return {
                "policy": self.policy,
                "segments": len(self._store),
                "bytes": self._bytes,
                "max_bytes": self.max_bytes,
                "host_segments": len(self._store) - swapped,
                "swap_segments": swapped,
                "swap_bytes": self._swap_bytes,
                "device_segments": sum(1 for e in self._store.values() if "kd" in e),
                "device_bytes": self._dev_bytes,
                "device_max_bytes": self.device_max_bytes,
                "page_segments": sum(1 for e in self._store.values() if "pages" in e),
                "page_bytes": page_bytes,
                "hbm_bytes": self._dev_bytes + page_bytes,
                "max_depth": max_depth,
                **self.stats,
            }


# the handler (and every test written against the flat cache) constructs
# ``PrefixCache``; the radix tree IS the prefix cache now, with the flat
# behavior preserved behind policy="lru"
PrefixCache = RadixPrefixCache

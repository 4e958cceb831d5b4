"""Automatic prefix caching: a device-side KV block pool + host block index.

Serving workloads repeat prompt prefixes constantly — shared system prompts,
multi-turn chats that resend the whole conversation each turn (the main
traffic shape of the tunnel's OpenAI surface; the reference forwards such
requests to Ollama, which recomputes the full prompt every time,
serve.rs:219).  This module skips that recompute: prompt KV is saved in
fixed-size blocks keyed by a chain hash of their token content, and a new
request's longest cached prefix is COPIED into its cache slot so prefill
only computes the tail (models/transformer.chunk_prefill_into_cache).

TPU-first design — copy, don't page:
- vLLM-style paged attention indirects every KV read through a block table,
  which XLA can't do without gathers in the decode hot loop.  Instead the
  pool is a dense ``[L, P, B, K, D]`` array and matched blocks are copied
  into the slot's contiguous cache region ONCE at admission — decode stays
  the existing dense/fused-slice path, completely unaware of the cache.
- Copies are two jitted programs with STATIC shapes: block ids are padded
  to the maximum count with clamped duplicate (index, value) pairs —
  duplicates write identical bytes, so scatter order cannot matter — and
  pool block 0 is a scratch target for insert padding.  One compile each,
  ever.
- Copy cost is bandwidth-trivial next to what it saves: a 48-token prefix
  of an 8B model is ~6 MB of KV (~8 us of HBM traffic) versus ~0.8 GFLOP
  of recompute per layer-stack pass.

Eviction is plain LRU over pool blocks.  Blocks are independent copies —
eviction never invalidates a running request (no refcounts, no page
tables).  Consistency: the host index is only touched from the engine's
event loop, and device copies dispatch through the engine's single XLA
executor thread, so a match's copy-in always executes before any later
insert that might recycle the matched block.

Tiered spill (ISSUE 16): the pool grows a pinned host-RAM tier.  Cold
pages (lowest GreedyDual priority) are paged out asynchronously as byte
payloads + integrity checksum + compatibility pin metadata; an evicted
page with a host shadow MIGRATES to the tier instead of dying, and a
returning prompt whose chain continues into the tier is spliced back via
a two-phase page-in (claim a slot on the event loop, copy + verify on
the executor).  Correctness never depends on the tier: a failed or
corrupt page-in drops the page and the request re-prefills its tail —
:func:`verify_page_pin` is the registered tier-boundary check tunnelcheck
TC18 enforces statically.
"""

from __future__ import annotations

import hashlib
import json
import os
from collections import OrderedDict
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from p2p_llm_tunnel_tpu.utils.logging import get_logger

log = get_logger(__name__)


class _Entry:
    """One pooled page's index record: pool slot, recompute-cost priority
    (GreedyDual, cost-aware mode), and the conversation tag (ISSUE 14:
    pages saved from a FINISHED stream's KV rather than a prompt insert)."""

    __slots__ = ("idx", "cost", "conv", "prio")

    def __init__(self, idx: int, cost: float = 0.0, conv: bool = False,
                 prio: float = 0.0):
        self.idx = idx
        self.cost = cost
        self.conv = conv
        self.prio = prio


class StateSnapshots:
    """Host index of the recurrent-state snapshots kept beside the pages
    (a family whose cache is rows a token AND a state a slot:
    models/ssm_moe.py).  A snapshot is the state after a whole number of
    blocks, keyed by the chain key of the last of them (which commits to
    the whole prefix); slot 0 of the snapshot arrays is the scratch target
    of padding.  Plain LRU, pure host state; pages and snapshots are
    evicted apart, and a match takes the longest pooled prefix that ends at
    a snapshot (:meth:`PrefixIndex.match`)."""

    def __init__(self, capacity: int):
        assert capacity >= 2, "need at least scratch + one snapshot"
        self.capacity = capacity
        self._free: List[int] = list(range(1, capacity))
        self._lru: "OrderedDict[bytes, int]" = OrderedDict()
        self.evictions = 0

    def __len__(self) -> int:
        return len(self._lru)

    def __contains__(self, key: bytes) -> bool:
        return key in self._lru

    def lookup(self, key: bytes) -> Optional[int]:
        """The snapshot taken at ``key``'s boundary (touched), or None."""
        idx = self._lru.get(key)
        if idx is not None:
            self._lru.move_to_end(key)
        return idx

    def allocate(self, key: bytes) -> int:
        """Where ``key``'s snapshot goes: its own slot if it has one, a
        free one, else the least recently used one's."""
        idx = self.lookup(key)
        if idx is None:
            if self._free:
                idx = self._free.pop()
            else:
                _old, idx = self._lru.popitem(last=False)
                self.evictions += 1
            self._lru[key] = idx
        return idx


class PagePinError(ValueError):
    """A KV page's compatibility pins don't match the engine's (quant mode,
    group size, kv_quant, dtype, block geometry): splicing its bytes would
    silently serve KV computed under different numerics.  Callers treat
    the page as lost and fall back to tail re-prefill.

    ``tunnel_code`` lets the serve layer mint the TYPED refusal when the
    mismatch crosses the tunnel (a disaggregated KV transfer, ISSUE 20) —
    carried only on the dedicated transfer stream, never a request stream.
    """

    tunnel_code = "page_pin"


def verify_page_pin(page, meta: Dict, want: Dict):
    """THE registered tier-boundary check (tunnelcheck TC18): every KV page
    crossing a tier or tunnel boundary must flow through here before its
    bytes are spliced into a pool or cache.  Returns ``page`` only when
    every pin in ``want`` matches the page's recorded ``meta`` — the same
    compatibility contract as the PR 2/3 snapshot-manifest pin loop,
    applied per page instead of per snapshot."""
    for key, val in want.items():
        if meta.get(key) != val:
            raise PagePinError(
                f"KV page pin mismatch on {key!r}: page carries "
                f"{meta.get(key)!r}, engine wants {val!r}"
            )
    return page


def page_checksum(payload: Dict[str, np.ndarray]) -> bytes:
    """Integrity digest over a host-tier page's raw bytes, leaf-name
    keyed so a leaf swap can't cancel out.  Verified on every page-in —
    a corrupt page must fall back to re-prefill, never splice."""
    h = hashlib.blake2b(digest_size=16)
    for key in sorted(payload):
        h.update(key.encode())
        h.update(np.ascontiguousarray(payload[key]).tobytes())
    return h.digest()


class _SpillPage:
    """One host-RAM tier page: the paged-out pool bytes (opaque to the
    index — a dict of per-leaf numpy arrays), an integrity checksum over
    them, the compatibility pin metadata that must ride every page across
    a tier boundary (TC18), and the GreedyDual accounting carried over
    from the HBM entry so tier-resident pages keep competing on recompute
    cost after they migrate."""

    __slots__ = ("payload", "checksum", "meta", "cost", "conv", "prio")

    def __init__(self, payload: Dict[str, np.ndarray], checksum: bytes,
                 meta: Dict, cost: float = 0.0, conv: bool = False,
                 prio: float = 0.0):
        self.payload = payload
        self.checksum = checksum
        self.meta = meta
        self.cost = cost
        self.conv = conv
        self.prio = prio


class PrefixIndex:
    """Host-side chain-hash index: block content -> pool slot, with
    LRU or cost-aware (GreedyDual) eviction.

    A block's key is ``blake2b(parent_digest || block_token_bytes)`` so
    equal token windows at different offsets/contexts never collide: block
    i's key commits to the ENTIRE prefix [0, (i+1)*block).

    The digest is cryptographic ON PURPOSE (ADVICE r4): Python's builtin
    tuple/int hash is an invertible algebraic mix, so two different
    prefixes can share a key by adversarial construction — and a collision
    here silently serves one request KV computed from another request's
    content.  vLLM moved its prefix keys from builtin hash to sha256 for
    the same reason; a 16-byte blake2b costs ~1 us per block.

    Eviction (ISSUE 14): ``evict="cost"`` runs GreedyDual — each page
    carries ``prio = clock + recompute_cost_ms`` refreshed on every touch,
    the victim is the minimum-priority page (ties broken by LRU order, so
    the policy is deterministic for a fixed operation sequence), and the
    clock advances to each victim's priority so long-idle pages age out
    regardless of cost.  ``recompute_cost_ms`` is the page's full-prefix
    token count times the engine's live per-token prefill-ms estimate —
    losing page i of a chain orphans every page after it, so deep
    (expensive, conversation-tail) pages outrank shallow cheap ones.
    ``evict="lru"`` restores the pre-ISSUE-14 plain LRU.  Pure host state;
    deterministic: same (insert, touch, cost) sequence, same evictions
    (tests/test_paged_pool.py two-run identity).
    """

    def __init__(self, block: int, capacity: int, evict: str = "lru",
                 spill_pages: int = 0):
        assert capacity >= 2, "need at least scratch + one real block"
        if evict not in ("lru", "cost"):
            raise ValueError(f"unknown evict mode {evict!r}")
        self.block = block
        self.capacity = capacity
        self.evict = evict
        # Pool index 0 is the scratch block (insert-padding target).
        self._free: List[int] = list(range(1, capacity))
        self._lru: "OrderedDict[bytes, _Entry]" = OrderedDict()
        self._clock = 0.0
        self._keys_memo: "OrderedDict[tuple, List[bytes]]" = OrderedDict()
        self.hits = 0
        self.lookups = 0
        # ISSUE 14 accounting: evictions + conversation-cache reuse, read
        # by the engine's delta-inc publisher, and the admission-time page
        # reservation tally (advisory; released grants must zero it — the
        # leak-gate invariant).
        self.evictions = 0
        self.conv_hits = 0
        self.conv_hit_tokens = 0
        self.reserved_pages = 0
        # Host-RAM spill tier (ISSUE 16).  ``_spill`` shadows pool pages by
        # chain key: while the key is also HBM-resident the shadow is a
        # pre-paid copy (eviction then migrates instead of destroying);
        # once evicted the shadow is the page's only body and a page-in
        # splices it back.  Event-loop-thread-only, like every index
        # structure — the executor copies bytes, the loop commits them.
        self.spill_pages = max(0, int(spill_pages))
        self._spill: "OrderedDict[bytes, _SpillPage]" = OrderedDict()
        self.spill_pageouts = 0
        self.spill_pageins = 0
        self.spill_drops = 0
        # Disaggregation (ISSUE 20): pages spliced from the WIRE (a prefill
        # peer's KV_PAGES transfer) rather than the host tier — same
        # two-phase path, separate tally so spill metrics stay honest.
        self.wire_spliced = 0
        # Thrash substrate: keys evicted recently enough that re-allocating
        # them signals reuse-distance > capacity (the detector's input).
        self._recent_evicted: "OrderedDict[bytes, float]" = OrderedDict()
        self.thrash_reallocs = 0
        # Where a cached token is not all a sequence carries (a recurrent
        # state a slot): the engine's StateSnapshots.  A match then ends at
        # the longest pooled boundary that has one.
        self.snapshots: Optional[StateSnapshots] = None

    @property
    def used_blocks(self) -> int:
        """Pool blocks currently holding cached KV (excludes scratch)."""
        return len(self._lru)

    @property
    def free_blocks(self) -> int:
        """Pool blocks available for insertion without an eviction."""
        return len(self._free)

    @property
    def spill_resident(self) -> int:
        """Host-tier pages currently held (shadows + host-only)."""
        return len(self._spill)

    def export_state(self) -> List[List]:
        """Snapshot rows: a leading ``["clock", value]`` row (the
        GreedyDual value floor — without it a restore replays saved prios
        against clock 0 and the first insert wave evicts every restored
        page first), then LRU-ordered ``[hex key, pool idx, cost, conv,
        prio]`` for HBM-resident pages, then ``[hex key, -1, ...]``
        tier-residency markers for host-only spilled pages.  ``idx == -1``
        means NOT HBM-resident: a restore must not resurrect these as pool
        pages — their bytes live in process RAM, which the snapshot file
        does not carry."""
        rows: List[List] = [["clock", round(self._clock, 3)]]
        rows += [
            [k.hex(), e.idx, round(e.cost, 3), int(e.conv),
             round(e.prio, 3)]
            for k, e in self._lru.items()
        ]
        rows += [
            [k.hex(), -1, round(p.cost, 3), int(p.conv), round(p.prio, 3)]
            for k, p in self._spill.items()
            if k not in self._lru
        ]
        return rows

    def import_state(self, entries: List[List]) -> None:
        """Restore a snapshot's index; unreferenced pool slots become free.
        Malformed entries are skipped — a damaged manifest must degrade to
        a (partially) cold pool, never crash engine startup.  Accepts the
        2-field pre-ISSUE-14 shape, the 4-field (cost, conv) ISSUE-14
        shape, and the 5-field (+prio) ISSUE-16 shape with its optional
        leading clock row.  Spilled-page markers (idx -1) are residency
        records only and are SKIPPED: the host-tier bytes died with the
        writing process, and resurrecting the key as HBM-resident would
        alias it to a pool block holding other content."""
        self._lru.clear()
        self._clock = 0.0
        used = set()
        for entry in entries:
            try:
                if entry[0] == "clock":
                    self._clock = float(entry[1])
                    continue
                khex, idx = entry[0], int(entry[1])
                key = bytes.fromhex(khex)
                cost = float(entry[2]) if len(entry) > 2 else 0.0
                conv = bool(entry[3]) if len(entry) > 3 else False
                prio = float(entry[4]) if len(entry) > 4 else cost
            except (TypeError, ValueError, IndexError):
                continue
            if not 1 <= idx < self.capacity or idx in used:
                # Out-of-range (larger pool / spilled-tier marker) or
                # duplicate index (damaged manifest): admitting it would
                # alias two prefix keys to one KV block — another prompt's
                # cache served silently.
                continue
            self._lru[key] = _Entry(idx, cost, conv, prio=prio)
            used.add(idx)
        self._free = [i for i in range(1, self.capacity) if i not in used]

    #: Bounded chain-key memo: one admitted request's prompt is hashed at
    #: up to THREE serving-path sites (reserve at admission, match at
    #: prefill planning, missing at insert) and herd prompts repeat —
    #: keyed by the exact token tuple so a hit can never alias.
    KEYS_MEMO_CAP = 128

    def _keys_of(self, prompt_ids) -> List[bytes]:
        memo_key = tuple(prompt_ids)
        hit = self._keys_memo.get(memo_key)
        if hit is not None:
            self._keys_memo.move_to_end(memo_key)
            return hit
        keys = []
        h = b""
        b = self.block
        for i in range(len(prompt_ids) // b):
            window = np.asarray(prompt_ids[i * b : (i + 1) * b], np.int64)
            h = hashlib.blake2b(
                h + window.tobytes(), digest_size=16
            ).digest()
            keys.append(h)
        self._keys_memo[memo_key] = keys
        if len(self._keys_memo) > self.KEYS_MEMO_CAP:
            self._keys_memo.popitem(last=False)
        return keys

    def match(self, prompt_ids) -> Tuple[int, List[int]]:
        """Longest cached prefix: (n_tokens, pool ids), possibly (0, []).

        Capped at ``(len(prompt)-1) // block`` blocks so at least one real
        token remains for the tail prefill (the first sampled token comes
        from the tail's last logits).  With :attr:`snapshots` the match
        ends at the longest pooled boundary that has a snapshot: rows
        without the state at their end restore nothing."""
        self.lookups += 1
        max_blocks = (len(prompt_ids) - 1) // self.block
        ids: List[int] = []
        conv_blocks = 0
        keys = self._keys_of(prompt_ids)[:max_blocks]
        if self.snapshots is not None:
            n = 0
            for i, key in enumerate(keys):
                if key not in self._lru:
                    break
                if key in self.snapshots:
                    n = i + 1
            keys = keys[:n]
        for key in keys:
            entry = self._lru.get(key)
            if entry is None:
                break
            self._lru.move_to_end(key)  # touched = most recent
            entry.prio = self._clock + entry.cost
            if entry.conv:
                conv_blocks += 1
            ids.append(entry.idx)
        if ids:
            self.hits += 1
        if conv_blocks:
            # Conversation reuse (ISSUE 14): this match reached INTO pages
            # saved from a finished stream — a returning user's history.
            self.conv_hits += 1
            self.conv_hit_tokens += conv_blocks * self.block
        return len(ids) * self.block, ids

    def id_of(self, key: bytes) -> Optional[int]:
        """Current pool slot for ``key`` (no LRU touch), or None if evicted
        — the engine's batched insert uses this to drop (key, id) pairs a
        later same-wave allocation evicted."""
        entry = self._lru.get(key)
        return None if entry is None else entry.idx

    def missing(self, prompt_ids) -> List[Tuple[int, bytes]]:
        """Fully-covered prompt blocks not yet pooled: [(block_no, key)]."""
        return [
            (i, key)
            for i, key in enumerate(self._keys_of(prompt_ids))
            if key not in self._lru
        ]

    def _pick_victim(self, protect: set) -> Optional[bytes]:
        """The next eviction victim, or None when every page is protected
        (allocated in the in-progress call).  "lru": the least-recently
        touched page.  "cost": the minimum-priority page, LRU order
        breaking ties — deterministic by OrderedDict iteration.

        With the spill tier active, both policies become CLEAN-FIRST
        (write-back cache discipline): a page with a host shadow is
        recoverable — evicting it is a tier migration — while evicting
        an unshadowed page destroys it and breaks its chain for every
        later turn.  Dirty pages are only taken when no clean candidate
        exists (the async cleaner is behind); the r16 herd measured the
        alternative — planned page-out victims evaporating between plan
        and commit under burst churn — as whole-chain loss that capped
        every returning match at the first dead block."""
        clean_tier = self.spill_pages > 0
        if self.evict == "lru":
            dirty_fallback = None
            for key in self._lru:
                if key in protect:
                    continue
                if clean_tier and key not in self._spill:
                    if dirty_fallback is None:
                        dirty_fallback = key
                    continue
                return key
            return dirty_fallback
        best_key, best_rank = None, None
        for pos, (key, entry) in enumerate(self._lru.items()):
            if key in protect:
                continue
            dirty = 1 if (clean_tier and key not in self._spill) else 0
            rank = (dirty, entry.prio, pos)
            if best_rank is None or rank < best_rank:
                best_key, best_rank = key, rank
        return best_key

    def _evict_one(self, protect: set) -> Optional[int]:
        """Evict one page (policy above); returns its freed pool idx."""
        victim = self._pick_victim(protect)
        if victim is None:
            return None
        entry = self._lru.pop(victim)
        # GreedyDual clock: the pool's value floor rises to each victim's
        # priority, so surviving pages age relative to it (an untouched
        # expensive page eventually loses to fresh cheap ones).
        self._clock = max(self._clock, entry.prio)
        self.evictions += 1
        page = self._spill.get(victim)
        if page is not None:
            # Tier migration, not loss: the host shadow (paged out earlier
            # by the spill drain) becomes the page's only body.  The HBM
            # entry's accounting rides along so a later page-in re-enters
            # GreedyDual competition where the page left off.
            page.cost, page.conv, page.prio = entry.cost, entry.conv, entry.prio
        # Thrash substrate: remember recent victims so a re-allocation of
        # the same chain key counts as a reuse-distance-over-capacity
        # event (the eviction-rate × reuse-distance detector's input).
        self._recent_evicted[victim] = self._clock
        while len(self._recent_evicted) > 4 * self.capacity:
            self._recent_evicted.popitem(last=False)
        return entry.idx

    def reserve(self, n: int) -> int:
        """Admission-time page reservation (ISSUE 14): make room for up to
        ``n`` pages NOW — evicting under the configured policy — and
        record the grant.  Returns the granted count; the caller must
        :meth:`release` exactly that many (on insert or on any death
        path).  Advisory accounting: allocation does not hard-partition
        the free list, it only pre-drains pressure off the serving wave.
        """
        grant = min(n, max(0, (self.capacity - 1) - self.reserved_pages))
        want = self.reserved_pages + grant
        while len(self._free) < want:
            idx = self._evict_one(set())
            if idx is None:
                break
            self._free.append(idx)
        self.reserved_pages += grant
        return grant

    def release(self, n: int) -> None:
        self.reserved_pages = max(0, self.reserved_pages - n)

    def allocate(self, keys: List[bytes], costs: Optional[List[float]] = None,
                 conv: bool = False) -> List[int]:
        """Assign a pool slot per key (evicting as needed); the caller
        must then actually copy the block content in.  ``costs`` (one per
        key, ms) feeds cost-aware eviction; ``conv`` tags the pages as
        conversation-cache content (finished-stream KV).

        May return FEWER ids than keys: allocation stops rather than evict
        a key allocated in this same call (a prompt with more blocks than
        the pool holds would otherwise get duplicate pool ids and
        self-cannibalized chains).  Keys are chain-ordered, so a prefix of
        the requested blocks is still a matchable chain prefix.
        """
        out: List[int] = []
        newly: set = set()
        for j, key in enumerate(keys):
            if self._free:
                idx = self._free.pop()
            else:
                idx = self._evict_one(newly)
                if idx is None:
                    break  # pool exhausted by this very call: stop
            cost = costs[j] if costs is not None else 0.0
            if key in self._recent_evicted:
                # The key was evicted recently and is being recomputed:
                # its reuse distance exceeds the pool — thrash, by
                # definition.  The engine's detector windows this counter.
                del self._recent_evicted[key]
                self.thrash_reallocs += 1
            if key in self._spill:
                # Fresh insert under a spilled key: the new bytes (a
                # re-prefill after a failed page-in, or a conversation-
                # cache overwrite) supersede the shadow — splicing the
                # stale shadow later would break byte identity.
                self._spill.pop(key)
                self.spill_drops += 1
            self._lru[key] = _Entry(idx, cost, conv,
                                    prio=self._clock + cost)
            newly.add(key)
            out.append(idx)
        return out

    # ------------------------------------------------------------------
    # Host-RAM spill tier (ISSUE 16).  All methods below are event-loop-
    # thread bookkeeping per the _release_pages contract: the engine plans
    # here, copies bytes on its executor, and commits back here.

    def spill_plan(self, n: int,
                   exclude: frozenset = frozenset()) -> List[Tuple[bytes, int]]:
        """The ``n`` lowest-priority HBM-resident pages with no host
        shadow yet: [(key, pool idx)] for the engine's async page-out
        batch.  Deterministic: (prio, LRU position) order, so a fixed
        operation sequence spills the same pages (two-run identity).
        ``exclude`` protects pages about to be matched this iteration."""
        if n <= 0 or self.spill_pages <= 0:
            return []
        cands = [
            (entry.prio, pos, key, entry.idx)
            for pos, (key, entry) in enumerate(self._lru.items())
            if key not in self._spill and key not in exclude
        ]
        cands.sort()
        return [(key, idx) for _, _, key, idx in cands[:n]]

    def note_spilled(self, key: bytes, payload: Dict[str, np.ndarray],
                     checksum: bytes, meta: Dict) -> bool:
        """Commit one completed page-out (event loop).  Rejected when the
        page was evicted mid-copy (its bytes may already be recycled) or
        already shadowed; makes room by dropping the least valuable
        host-tier page when the tier is full."""
        entry = self._lru.get(key)
        if entry is None or key in self._spill:
            return False
        self._spill_make_room()
        self._spill[key] = _SpillPage(payload, checksum, meta,
                                      entry.cost, entry.conv, entry.prio)
        self.spill_pageouts += 1
        return True

    def _spill_make_room(self) -> None:
        """Cap the host tier at ``spill_pages``: drop shadows of still-
        HBM-resident pages first (nothing is lost — the pool copy lives
        on), then the lowest-priority host-only page."""
        while len(self._spill) >= max(1, self.spill_pages):
            best_key, best_rank = None, None
            for pos, (key, page) in enumerate(self._spill.items()):
                rank = (0 if key in self._lru else 1, page.prio, pos)
                if best_rank is None or rank < best_rank:
                    best_key, best_rank = key, rank
            self._spill.pop(best_key)
            self.spill_drops += 1

    def spill_extension(self, prompt_ids) -> List[Tuple[int, bytes]]:
        """Host-tier pages that would EXTEND this prompt's HBM match:
        [(block_no, key)] of spilled (host-only) chain keys past the
        resident prefix, skipping keys already resident (match resumes
        through those once the gap is spliced), stopping at the first key
        in neither tier.  Capped like :meth:`match` so a tail token
        remains for prefill."""
        if not self._spill:
            return []
        max_blocks = (len(prompt_ids) - 1) // self.block
        keys = self._keys_of(prompt_ids)[:max_blocks]
        i = 0
        while i < len(keys) and keys[i] in self._lru:
            i += 1  # HBM-resident prefix: match() already serves it
        out: List[Tuple[int, bytes]] = []
        for j in range(i, len(keys)):
            key = keys[j]
            if key in self._lru:
                continue
            if key in self._spill:
                out.append((j, key))
            else:
                break
        return out

    def chain_keys(self, prompt_ids) -> List[bytes]:
        """ALL of the prompt's matchable chain keys (no LRU touch) — the
        eviction-protection set a page-in slot claim must honor.  The
        whole chain, not just the contiguous resident prefix: a chain
        whose block 0 died still holds matchable mid-chain residents
        that the SAME wave's splice is about to reconnect, and claiming
        slots by evicting them converts the splice into churn (the r16
        80-client herd measured 881 splices/turn yielding ~3 matches
        under prefix-only protection)."""
        max_blocks = (len(prompt_ids) - 1) // self.block
        return list(self._keys_of(prompt_ids)[:max_blocks])

    def block_keys(self, ids) -> List[bytes]:
        """The chain keys of every whole block of ``ids`` (no LRU touch, no
        cap: the last one names the boundary at ``len(ids)`` where that is
        whole blocks)."""
        return list(self._keys_of(ids))

    def touch_resident(self, keys) -> None:
        """MRU-touch the resident members of a page-in wave's protection
        set.  The wave's match runs later in the SAME iteration, but
        admission's own reserve/insert evictions run in between — and a
        chain untouched for a whole conversation turn sits exactly at
        the LRU tail those evictions harvest.  Touching moves 'about to
        be matched' ahead of genuinely cold pages in the LRU order;
        pages the match then fails to use simply age out again."""
        for key in keys:
            if key in self._lru:
                self._lru.move_to_end(key)

    def page_in_alloc(self, keys: List[bytes],
                      protect: frozenset = frozenset(),
                      offered: "Optional[Dict[bytes, _SpillPage]]" = None,
                      ) -> List[Tuple[bytes, int, "_SpillPage"]]:
        """Two-phase page-in, phase 1 (event loop): claim one free pool
        slot per host-tier key — evicting under the policy, never a
        ``protect`` key — WITHOUT touching the index.  The caller copies
        bytes on the executor, then finishes every claim with
        :meth:`commit_page_in` or :meth:`abort_page_in`; until then the
        claimed slot is invisible to match/allocate (it is simply not in
        ``_free``), so a racing insert can never alias it.

        ``offered`` (ISSUE 20) sources pages from a caller-supplied map
        instead of the host tier — a prefill peer's KV_PAGES transfer
        rides the SAME claim/verify/commit discipline as a spill
        page-in, it just arrives over the tunnel instead of process RAM.
        """
        out: List[Tuple[bytes, int, _SpillPage]] = []
        prot = set(protect)
        for key in keys:
            page = (self._spill.get(key) if offered is None
                    else offered.get(key))
            if page is None or key in self._lru:
                continue
            if self._free:
                idx = self._free.pop()
            else:
                idx = self._evict_one(prot)
                if idx is None:
                    break
            out.append((key, idx, page))
        return out

    def commit_page_in(self, key: bytes, idx: int,
                       page: "Optional[_SpillPage]" = None) -> None:
        """Phase 2 success: the verified bytes are in pool slot ``idx`` —
        insert the entry (fresh GreedyDual touch) and count the splice.
        The shadow stays: its bytes still match the pool copy, so a later
        eviction migrates back to the tier without another copy.

        ``page`` (ISSUE 20) carries the accounting for a wire-offered
        page that has no host-tier shadow; wire splices tally separately
        so the spill counters stay honest."""
        from_tier = page is None
        if from_tier:
            page = self._spill.get(key)
        cost = page.cost if page is not None else 0.0
        conv = page.conv if page is not None else False
        self._lru[key] = _Entry(idx, cost, conv, prio=self._clock + cost)
        if from_tier:
            self.spill_pageins += 1
        else:
            self.wire_spliced += 1

    def abort_page_in(self, key: bytes, idx: int) -> None:
        """Phase 2 failure (chaos fail/stall, checksum or pin mismatch):
        return the claimed slot and DROP the host page — correctness falls
        back to tail re-prefill, never to suspect bytes."""
        if self._spill.pop(key, None) is not None:
            self.spill_drops += 1
        self._free.append(idx)


def plan_group_admission(
    index: PrefixIndex,
    inflight: Dict[bytes, int],
    wave: List[Tuple[int, List[int]]],
) -> Tuple[List[Tuple[int, int, List[int], List[bytes]]],
           List[Tuple[int, int]]]:
    """Prefix-aware batched admission planning (ISSUE 5, AlignedServe):
    decide, for a FIFO wave of admitted requests, who PREFILLS and who
    WAITS, so a shared not-yet-pooled prefix is computed exactly once.

    ``wave`` is ``[(rid, prompt_ids)]`` in FIFO order.  ``inflight`` maps
    chain keys of prompt blocks currently being prefilled by an admitted
    request to the owning rid, and is UPDATED IN PLACE (new owners
    register their missing block keys).  Pure host logic — no device work
    — so the admission loop stays dispatch-free per request (TC07) and the
    fairness properties are unit-testable (tests/test_mux.py).

    Returns ``(owners, waiters)``:

    - ``owners`` — ``[(rid, hist_tokens, pool_ids, missing_keys)]``:
      proceed now; their pooled prefix (``hist_tokens`` tokens via
      ``pool_ids``) is copied in and the tail prefills.  ``missing_keys``
      are the chain keys this request will compute and later insert; the
      caller must release them (and re-plan this owner's waiters) when the
      prefill completes or the request dies.
    - ``waiters`` — ``[(rid, owner_rid)]``: the request's FIRST missing
      block is already being computed by ``owner_rid``.  Chain keys commit
      to the whole prefix (block i's key hashes blocks [0, i]), so sharing
      that one key proves the waiter's entire uncached prefix up to and
      including it is the owner's work — park, and re-plan against the
      pool once the owner's blocks land.

    FIFO is preserved within a group by construction: the owner is the
    group's first-arriving member (earlier wave entries register keys
    before later ones consult them), and callers wake waiters in arrival
    order.
    """
    owners: List[Tuple[int, int, List[int], List[bytes]]] = []
    waiters: List[Tuple[int, int]] = []
    for rid, prompt_ids in wave:
        hist, ids = index.match(prompt_ids)
        missing = index.missing(prompt_ids)
        if missing:
            first_key = missing[0][1]
            owner = inflight.get(first_key)
            if owner is not None and owner != rid:
                waiters.append((rid, owner))
                continue
        keys = [k for _, k in missing]
        for k in keys:
            inflight[k] = rid  # tunnelcheck: disable=TC15  cross-function lifecycle: released by engine._owner_done — on finish via _finish_segments -> _mux_wake, and on owner death via _mux_wake's per-iteration alive sweep (a dead owner's claims are dropped so waiters re-plan, never park forever)
        owners.append((rid, hist, ids, keys))
    return owners, waiters


def save_pool_snapshot(
    dirpath: str, pool: Dict[str, jnp.ndarray], index: PrefixIndex,
    meta: Dict,
) -> None:
    """Persist the block pool + index so warm prompt KV survives a serve
    restart (SURVEY §5's optional checkpoint clause, VERDICT r4 item 10).

    Plain npz + json: the pool is a handful of dense host-shaped arrays
    (~0.27 GB at 8B/128 blocks), not a sharded training state — orbax
    machinery buys nothing here.  The manifest pins every compatibility
    axis; loaders ignore any snapshot that doesn't match exactly."""
    import time

    os.makedirs(dirpath, exist_ok=True)
    # tmp + rename per file, PLUS a shared snap_id in both: a crash
    # between the two renames must not pair new pool bytes with the old
    # index (recycled block ids would silently serve another prompt's KV).
    snap_id = f"{time.time_ns():x}"
    host_pool = {k: np.asarray(v) for k, v in pool.items()}
    npz_tmp = os.path.join(dirpath, ".prefix_pool.npz.tmp")
    with open(npz_tmp, "wb") as f:
        np.savez(
            f,
            __snap_id__=np.frombuffer(snap_id.encode(), np.uint8),
            **host_pool,
        )
    os.replace(npz_tmp, os.path.join(dirpath, "prefix_pool.npz"))
    # version 3 (ISSUE 18): the manifest carries page_checksum over the
    # pool leaves — the same digest the spill tier verifies per page-in —
    # so the loader can refuse bytes damaged (or swapped) after the save.
    manifest = dict(meta, lru=index.export_state(), version=3,
                    snap_id=snap_id,
                    pool_checksum=page_checksum(host_pool).hex())
    man_tmp = os.path.join(dirpath, ".prefix_index.json.tmp")
    with open(man_tmp, "w") as f:
        json.dump(manifest, f)
    os.replace(man_tmp, os.path.join(dirpath, "prefix_index.json"))
    log.info("prefix pool snapshot saved: %d blocks -> %s",
             len(index._lru), dirpath)


def load_pool_snapshot(
    dirpath: str, pool: Dict[str, jnp.ndarray], index: PrefixIndex,
    meta: Dict,
) -> Optional[Dict[str, jnp.ndarray]]:
    """Restore a snapshot into a freshly-initialised pool; None (and an
    untouched index) when absent or incompatible."""
    man_path = os.path.join(dirpath, "prefix_index.json")
    npz_path = os.path.join(dirpath, "prefix_pool.npz")
    if not (os.path.exists(man_path) and os.path.exists(npz_path)):
        return None
    try:
        with open(man_path) as f:
            manifest = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        log.warning("prefix snapshot unreadable (%s); starting cold", e)
        return None
    if manifest.get("version") != 3:
        # Version 2 manifests carry no pool_checksum: their bytes are
        # unverifiable, so they are refused rather than grandfathered.
        log.warning("prefix snapshot version %r unsupported (current: 3); starting cold",
                    manifest.get("version"))
        return None
    try:
        # The manifest IS the snapshot's pin metadata; route it through
        # THE registered tier-boundary check (TC18/TC20) rather than an
        # inline comparison, so the snapshot import obeys the same page
        # wire contract as every spill-tier page-in.
        verify_page_pin(None, manifest, meta)
    except PagePinError as e:
        log.warning("prefix snapshot incompatible (%s); starting cold", e)
        return None
    try:
        npz = np.load(npz_path)
        files = set(npz.files)
        snap_id = bytes(npz["__snap_id__"]).decode()
    except Exception as e:  # BadZipFile/OSError/EOFError — corrupt file
        log.warning("prefix snapshot unreadable (%s); starting cold", e)
        return None
    if snap_id != manifest.get("snap_id"):
        # Crash between the pool and manifest renames: the halves are from
        # different saves and the index would point into the wrong blocks.
        log.warning("prefix snapshot halves mismatch (%s != %s); "
                    "starting cold", snap_id, manifest.get("snap_id"))
        return None
    if files - {"__snap_id__"} != set(pool):
        log.warning("prefix snapshot leaves mismatch; starting cold")
        return None
    host = {}
    for key, arr in pool.items():
        try:
            loaded = npz[key]
        except Exception as e:  # corrupt zip member surfaces on read
            log.warning("prefix snapshot unreadable (%s); starting cold", e)
            return None
        if loaded.shape != arr.shape:
            log.warning("prefix snapshot shape mismatch on %s; starting cold",
                        key)
            return None
        host[key] = loaded
    # Integrity gate (ISSUE 18): recompute the save-time digest over the
    # bytes we actually read.  The zip CRC only catches in-member rot;
    # a rewritten/swapped npz passes it — page_checksum is end-to-end.
    got = page_checksum(host).hex()
    if got != manifest.get("pool_checksum"):
        log.warning("prefix snapshot pool checksum mismatch (%s != %s); "
                    "starting cold", got, manifest.get("pool_checksum"))
        return None
    out = {key: jnp.asarray(host[key], arr.dtype)
           for key, arr in pool.items()}
    index.import_state(manifest.get("lru", []))
    log.info("prefix pool snapshot restored: %d blocks from %s",
             len(index._lru), dirpath)
    return out


def pool_packed_keys(kv_cache: Dict[str, jnp.ndarray]) -> frozenset:
    """The cache leaves whose sequence axis is BYTE-packed (two tokens per
    byte — the kv_quant="int4" value planes, recognized the same way
    transformer.kv_cache_quant_mode does).  Pages of these leaves are
    ``block // 2`` bytes; everything else (scales, unquantized caches) is
    ``block`` positions."""
    if ("k_scale" in kv_cache
            and kv_cache["k"].shape[2] * 2 == kv_cache["k_scale"].shape[2]):
        return frozenset({"k", "v"})
    return frozenset()


def init_pool(kv_cache: Dict[str, jnp.ndarray], block: int, capacity: int):
    """Pool arrays mirroring the cache dict's dtypes: cache [L, Slots, S, ...]
    -> pool [L, capacity, block, ...].  Packed int4 value leaves store
    ``block // 2`` BYTES per page (``block`` must be even under int4 —
    the ISSUE 14 page-alignment guarantee the engine enforces)."""
    packed = pool_packed_keys(kv_cache)
    if packed and block % 2:
        raise ValueError(
            f"packed int4 pool pages must be even-sized, got block={block}"
        )
    return {
        key: jnp.zeros(
            (arr.shape[0], capacity,
             block // 2 if key in packed else block) + arr.shape[3:],
            arr.dtype,
        )
        for key, arr in kv_cache.items()
    }


def make_copy_ops(block: int, max_blocks: int,
                  packed_keys: frozenset = frozenset()):
    """The two jitted copy programs, closed over static (block, max_blocks).

    Both take ``ids``/``blk_nos`` arrays of length EXACTLY ``max_blocks``
    and ``n`` is pre-applied by the caller via clamping (see pad_ids) —
    shapes never depend on the match length, so each op compiles once.
    ``packed_keys`` leaves move in ``block // 2``-byte page units (the
    int4 value planes); positions stay whole-byte by the page-alignment
    contract.
    """

    def _pos(unit):
        offs = jnp.arange(unit)[None, :]
        return lambda blk_nos: (blk_nos[:, None] * unit + offs).reshape(-1)

    def blocks_to_cache(cache, pool, slot, pool_ids, blk_nos):
        """cache[slot] positions [blk_no*B, +B) <- pool[pool_ids]."""
        out = dict(cache)
        for key, arr in cache.items():
            unit = block // 2 if key in packed_keys else block
            pos = _pos(unit)(blk_nos)  # [Nmax*unit]
            vals = pool[key][:, pool_ids]  # [L, Nmax, unit, ...]
            flat = vals.reshape((vals.shape[0], -1) + vals.shape[3:])
            out[key] = arr.at[:, slot, pos].set(flat)
        return out

    def cache_to_pool(pool, cache, slot, pool_ids, blk_nos):
        """pool[pool_ids] <- cache[slot] positions [blk_no*B, +B)."""
        out = dict(pool)
        for key, arr in pool.items():
            unit = block // 2 if key in packed_keys else block
            pos = _pos(unit)(blk_nos)
            vals = cache[key][:, slot, pos]  # [L, Nmax*unit, ...]
            vals = vals.reshape(
                (vals.shape[0], max_blocks, unit) + vals.shape[2:]
            )
            out[key] = arr.at[:, pool_ids].set(vals)
        return out

    return (
        jax.jit(blocks_to_cache, donate_argnums=(0,)),
        jax.jit(cache_to_pool, donate_argnums=(0,)),
    )


def plan_inserts(
    index: PrefixIndex, wave: List[Tuple[int, List[int]]],
    ms_per_token: float = 1.0, conv: bool = False,
) -> List[Tuple[int, List[int], List[int]]]:
    """Host-side planning for a batched pool insert: allocate blocks for
    every run's missing prompt blocks, then drop pairs a later same-wave
    allocation evicted.

    ``ms_per_token`` prices each page for cost-aware eviction — page i's
    recompute cost is its FULL-PREFIX token count ``(i+1) * block`` times
    it, since losing page i orphans every deeper page of the chain.
    ``conv`` tags the pages as conversation-cache content (ISSUE 14:
    finished-stream KV saved by the engine's end-of-iteration drain).

    ``wave`` is [(slot, prompt_ids)].  All index updates happen here for
    the WHOLE wave before any device copy; with a tiny pool and a big wave
    a later run's allocation may evict an earlier run's fresh key, and
    writing both into one batched scatter would leave the block holding
    whichever content the scatter ordered last while the index points at
    one of them.  The filter keeps only (key, id) pairs the index still
    maps exactly as allocated — the index is a bijection (one id per key),
    so surviving ids are wave-distinct and every surviving write is the
    content its key names.

    Returns [(slot, pool_ids, blk_nos)] ready for :func:`pad_rows`.
    """
    allocs: List[Tuple[int, List[bytes], List[int], List[int]]] = []
    for slot, prompt_ids in wave:
        missing = index.missing(prompt_ids)
        if not missing:
            continue
        keys = [k for _, k in missing]
        blk_nos = [i for i, _ in missing]
        costs = [(i + 1) * index.block * ms_per_token for i, _ in missing]
        # allocate() may return a PREFIX of the request when the pool is
        # smaller than the prompt; insert exactly what got ids.
        ids = index.allocate(keys, costs=costs, conv=conv)
        if ids:
            allocs.append((slot, keys[: len(ids)], blk_nos[: len(ids)], ids))
    entries: List[Tuple[int, List[int], List[int]]] = []
    seen: set = set()
    for slot, keys, blks, ids in allocs:
        # The per-wave ``seen`` dedupe closes the remaining aliasing hole:
        # two runs sharing a prompt can BOTH end up with the same live
        # (key, id) pair when eviction ping-pongs the id (A allocates k->i,
        # C evicts k reusing i, D re-allocates k back onto i).  Both writes
        # would hold KV of the same token prefix, but a duplicate id in one
        # scatter is formally nondeterministic — keep the first pair only.
        live = [
            (i, b)
            for k, b, i in zip(keys, blks, ids)
            if index.id_of(k) == i and i not in seen
        ]
        if live:
            seen.update(i for i, _ in live)
            entries.append(
                (slot, [i for i, _ in live], [b for _, b in live])
            )
    return entries


def make_batch_copy_ops(block: int, max_blocks: int, rows: int,
                        packed_keys: frozenset = frozenset(),
                        layerwise_keys: frozenset = frozenset(),
                        ring_keys: frozenset = frozenset()):
    """Row-batched copy programs: ONE dispatch serves up to ``rows``
    requests' block copies.

    Per-request copy dispatches serialize on the engine's XLA executor
    ahead of the wave's prefills, and each dispatch costs a host↔device
    round trip — a 32-client admission wave pays ~32 extra round trips
    inside its prefill path.  Batching the wave's copies into one program
    makes the prefix-cache dispatch cost O(1) per wave instead of
    O(clients).

    Same static-shape discipline as :func:`make_copy_ops`: ids pad
    within-row (clamped duplicate pairs / scratch block 0) AND across rows
    (row 0 repeated, or all-scratch rows), so each op compiles once ever.
    ``packed_keys`` leaves (the int4 value planes) move in
    ``block // 2``-byte page units — pages stay whole-byte by the ISSUE 14
    alignment guarantee, so packed copies are plain scatters too.
    ``layerwise_keys`` leaves are read from and written to the CACHE one
    layer at a time: a plane of one row of values a token and no head axis
    (the latent cache, models/mla.py) with the layer axis in a gather's or
    scatter's window is turned layers-innermost for it by the chip's
    compiler, whole, and back.  The pool side keeps whole pages in its
    window either way.
    ``ring_keys`` leaves are rings in the CACHE (a window layer's planes,
    models/swa.py: ``R`` positions a slot, position ``p`` at ``p % R``) and
    whole pages in the pool, like any leaf: a pooled token holds its window
    layers' keys and values too.  A restore writes only the last ``R``
    positions of the row's prefix (its blocks are numbered from 0, so its
    length is its largest block number's end): earlier ones would land on
    the same ring slots, and no window reaches them.  A save reads each
    block at its positions' ring slots, so a block is saved while the ring
    still holds it: the engine saves a prompt's blocks segment by segment.
    """

    def _pos(unit, blk_nos):
        offs = jnp.arange(unit)[None, None, :]
        return (blk_nos[:, :, None] * unit + offs).reshape(rows, -1)

    def _ring_slots(pos, blk_nos, ring):
        """Ring slots of ``pos [R, n]`` for a restore of the blocks
        ``blk_nos``; ``ring`` itself (dropped by the scatter) for positions
        more than a ring before the prefix's end."""
        end = (blk_nos.max(axis=1, keepdims=True) + 1) * block
        return jnp.where(pos >= end - ring, pos % ring, ring)

    def blocks_to_cache(cache, pool, slots, pool_ids, blk_nos):
        """cache[slots[r]] positions [blk_nos[r,i]*B, +B) <- pool[pool_ids[r,i]].

        slots [R]; pool_ids/blk_nos [R, Nmax].  Padding rows repeat a real
        row — duplicate scatters write identical bytes, so order cannot
        matter."""
        out = dict(cache)
        with jax.named_scope("pool_copy"):
            # (the pool's leaves: a cache may hold more than rows a token,
            # a recurrent state a slot, which passes as it is)
            for key in pool:
                arr = cache[key]
                unit = block // 2 if key in packed_keys else block
                pos = _pos(unit, blk_nos)
                vals = pool[key][:, pool_ids]  # [L, R, Nmax, unit, ...]
                flat = vals.reshape(
                    (vals.shape[0], rows, pos.shape[1]) + vals.shape[4:]
                )
                if key in ring_keys:
                    pos = _ring_slots(pos, blk_nos, arr.shape[2])
                if key in layerwise_keys:
                    for i in range(arr.shape[0]):
                        arr = arr.at[i, slots[:, None], pos].set(flat[i])
                    out[key] = arr
                else:
                    out[key] = arr.at[:, slots[:, None], pos].set(flat)
        return out

    def cache_to_pool(pool, cache, slots, pool_ids, blk_nos):
        """pool[pool_ids[r,i]] <- cache[slots[r]]; padding (within-row and
        whole rows) targets the scratch pool block 0, which is never
        matched.  Real pool ids must be wave-distinct — the caller filters
        same-wave eviction casualties so the flat scatter never writes two
        different contents to one live block."""
        flat_ids = pool_ids.reshape(-1)
        out = dict(pool)
        with jax.named_scope("pool_copy"):
            for key, arr in pool.items():
                unit = block // 2 if key in packed_keys else block
                pos = _pos(unit, blk_nos)
                if key in ring_keys:
                    pos = pos % cache[key].shape[2]
                if key in layerwise_keys:
                    vals = jnp.stack([
                        cache[key][i, slots[:, None], pos]
                        for i in range(arr.shape[0])])
                else:
                    vals = cache[key][:, slots[:, None], pos]  # [L, R, Nmax*unit, ...]
                vals = vals.reshape(
                    (vals.shape[0], rows * max_blocks, unit) + vals.shape[3:]
                )
                out[key] = arr.at[:, flat_ids].set(vals)
        return out

    return (
        jax.jit(blocks_to_cache, donate_argnums=(0,)),
        jax.jit(cache_to_pool, donate_argnums=(0,)),
    )


def make_state_copy_ops(keys: Tuple[str, ...], rows: int):
    """The two copy programs of a recurrent state's snapshots, batched like
    the pages': ``restore(cache, snaps, slots [R], ids [R])`` lays snapshot
    ``ids[r]`` of every leaf in ``keys`` over cache row ``slots[r]``
    (``[L, rows, ...]`` <- ``[L, capacity, ...]``), ``save(snaps, cache,
    slots, ids)`` the other way.  Padding rows name the scratch slot and
    snapshot 0 on both sides: duplicates write identical bytes.  A row at a
    time, by slices at traced indices: a gather of two rows out of a leaf
    of gigabytes read the leaf (9 % of the device in the first chip run)."""

    def _move(dst, src, to, frm):
        for r in range(rows):
            row = jax.lax.dynamic_slice_in_dim(src, frm[r], 1, axis=1)
            dst = jax.lax.dynamic_update_slice_in_dim(dst, row, to[r], axis=1)
        return dst

    def restore(cache, snaps, slots, ids):
        out = dict(cache)
        with jax.named_scope("state_write"):
            for key in keys:
                out[key] = _move(cache[key], snaps[key], slots, ids)
        return out

    def save(snaps, cache, slots, ids):
        with jax.named_scope("state_read"):
            return {key: _move(snaps[key], cache[key], ids, slots)
                    for key in keys}

    return (jax.jit(restore, donate_argnums=(0,)),
            jax.jit(save, donate_argnums=(0,)))


def make_spill_ops():
    """The two jitted single-page tier-I/O programs (ISSUE 16).

    ``page_out`` gathers one pool page's leaves (the executor then
    ``np.asarray``s the result into pinned host RAM); ``page_in`` scatters
    verified host bytes back into a claimed pool slot.  ``idx`` is a
    TRACED int32 — python-int indexing would specialize the program per
    slot and compile ``capacity`` times; ``dynamic_index_in_dim`` /
    ``dynamic_update_index_in_dim`` keep it to one compile each, ever."""

    def page_out(pool, idx):
        return {
            key: jax.lax.dynamic_index_in_dim(arr, idx, axis=1,
                                              keepdims=False)
            for key, arr in pool.items()
        }

    def page_in(pool, idx, page):
        out = dict(pool)
        for key, arr in pool.items():
            out[key] = jax.lax.dynamic_update_index_in_dim(
                arr, page[key].astype(arr.dtype), idx, axis=1
            )
        return out

    return jax.jit(page_out), jax.jit(page_in, donate_argnums=(0,))


def pad_rows(
    entries: List[Tuple[int, List[int], List[int]]],
    rows: int, max_blocks: int, scratch: Optional[int],
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Pad ``[(slot, pool_ids, blk_nos)]`` to the static [R]/[R, Nmax]
    shapes of :func:`make_batch_copy_ops`.

    Within-row padding follows :func:`pad_ids` (duplicate last pair /
    scratch target); missing rows repeat row 0 for cache<-pool copies
    (identical duplicate writes) or write scratch-only rows for
    pool<-cache copies."""
    assert 0 < len(entries) <= rows
    slots: List[int] = []
    pids: List[List[int]] = []
    bnos: List[List[int]] = []
    for slot, ids, blks in entries:
        n = len(ids)
        assert 0 < n <= max_blocks and len(blks) == n
        pad = scratch if scratch is not None else ids[-1]
        slots.append(slot)
        pids.append(list(ids) + [pad] * (max_blocks - n))
        bnos.append(list(blks) + [blks[-1]] * (max_blocks - n))
    while len(slots) < rows:
        slots.append(slots[0])
        pids.append([scratch] * max_blocks if scratch is not None
                    else pids[0])
        bnos.append(bnos[0])
    return (
        jnp.asarray(slots, jnp.int32),
        jnp.asarray(pids, jnp.int32),
        jnp.asarray(bnos, jnp.int32),
    )


def pad_ids(
    ids: List[int], blk_nos: List[int], max_blocks: int, scratch: Optional[int]
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Pad (pool_ids, block_nos) to the static length.

    For cache<-pool copies (``scratch is None``) padding repeats the LAST
    real pair — duplicate scatters then write identical values, so the
    result is deterministic and nothing past the real blocks is touched.
    For pool<-cache copies padding targets the scratch pool block 0, which
    is never matched.
    """
    n = len(ids)
    assert 0 < n <= max_blocks
    if scratch is None:
        pids = ids + [ids[-1]] * (max_blocks - n)
        bnos = blk_nos + [blk_nos[-1]] * (max_blocks - n)
    else:
        pids = ids + [scratch] * (max_blocks - n)
        bnos = blk_nos + [blk_nos[-1]] * (max_blocks - n)
    return jnp.asarray(pids, jnp.int32), jnp.asarray(bnos, jnp.int32)

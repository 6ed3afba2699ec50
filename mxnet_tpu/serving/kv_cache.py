"""KV-cache capacity accounting for continuous-batching decode.

:class:`PagedKVCacheManager` is the host-side ledger of the **paged
pool**: device K/V lives in fixed-size blocks (``block_size`` tokens
each) drawn from a shared :class:`BlockAllocator`; each request owns a
*block list* that holds the tokens it has written and grows with them, a
block at a time as a step is queued, while its ``prompt_len +
max_new_tokens`` budget stays on the books as a claim: a block is
granted, and a request admitted, only if every admitted request can
still finish afterwards (the safe-state rule,
:meth:`PagedKVCacheManager._safe`).  So pool capacity is bounded by
tokens in flight, not by ``max_len × num_slots`` nor by the budgets: a
long-prompt + short-prompt mix whose worst-case reservations exceed the
pool outright is admitted (the r11 capacity acceptance test).

The manager's transitions (``admit`` / ``advance`` / ``consume`` /
``evict``) come with ``check()`` invariants and ``stats()`` with
fragmentation and peak-token occupancy.  It is touched by TWO lane
threads (prefill admits, decode grants/advances/evicts —
docs/serving.md) and serializes its transitions on an internal lock.

Device-side block contents are the engine's problem: a freshly
allocated block may hold a previous tenant's K/V, but the per-slot
causal mask (``t <= pos``) hides every position the current request has
not yet written, so stale rows are unreachable.
"""
from __future__ import annotations

import threading

from ..base import MXNetError
from ..models.decoder import CacheSpec  # noqa: F401  (its home; kept importable here)

__all__ = ["PagedKVCacheManager", "BlockAllocator", "SlotState",
           "CacheSpec"]


class SlotState:
    """One occupied slot's bookkeeping."""

    __slots__ = ("request_id", "pos", "remaining", "joined_step",
                 "blocks", "reserved", "kept")

    def __init__(self, request_id, pos, remaining, joined_step,
                 blocks=None, reserved=0, kept=0):
        self.request_id = request_id
        self.pos = pos              # next cache row the step writes
        self.remaining = remaining  # tokens still owed to the request
        self.joined_step = joined_step
        self.blocks = blocks or []  # paged: block ids held, logical order
        # paged: the token budget (prompt + max_new_tokens): the most
        # the cursor may reach, and in blocks the request's claim
        self.reserved = reserved
        # paged: the leading blocks that another holder has or may take
        # a reference on (a shared prompt prefix): the safe-state rule
        # does not count on their return when the request ends
        self.kept = kept


class BlockAllocator:
    """Fixed-size KV block pool: ``num_blocks`` blocks of
    ``block_size`` tokens each, free-list allocation.

    ``alloc`` is all-or-nothing (a prompt's blocks or a grant come
    whole or not at all — nothing partial to unwind), and
    ``free`` rejects double-frees and foreign ids.

    Since r19 every allocated block carries a **refcount**: ``alloc``
    hands out blocks at refcount 1, ``share`` grants an additional
    holder (the radix prefix cache, or a request reusing a cached
    prefix), and ``release`` drops one reference — the block returns to
    the free list only at refcount 0.  ``free`` is ``release`` under
    its historical name, so single-holder callers behave exactly as
    before (including the double-free guard).  Shared blocks are
    strictly read-shared: only *full prompt-prefix* blocks are ever
    shared, and no decode or verify write targets a row inside them.
    """

    def __init__(self, num_blocks, block_size):
        if num_blocks < 1 or block_size < 1:
            raise MXNetError("num_blocks and block_size must be >= 1")
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self._free = list(range(self.num_blocks - 1, -1, -1))  # pop()->0
        self._in_use = set()
        self._refs = {}             # block id -> holder count (>= 1)
        self._peak_in_use = 0
        self._peak_shared = 0

    @property
    def free_blocks(self):
        return len(self._free)

    @property
    def blocks_in_use(self):
        return len(self._in_use)

    @property
    def peak_blocks_in_use(self):
        return self._peak_in_use

    @property
    def shared_blocks(self):
        """Blocks currently held by more than one owner."""
        return sum(1 for c in self._refs.values() if c > 1)

    @property
    def peak_shared_blocks(self):
        return self._peak_shared

    def refcount(self, block):
        """Holder count for ``block`` (0 when free)."""
        return self._refs.get(block, 0)

    def alloc(self, n):
        """Claim ``n`` blocks (ascending ids) at refcount 1.  Returns
        the id list, or None when the pool cannot cover the request
        (all-or-nothing)."""
        if n < 0:
            raise MXNetError(f"cannot allocate {n} blocks")
        if n > len(self._free):
            return None
        blocks = [self._free.pop() for _ in range(n)]
        self._in_use.update(blocks)
        for b in blocks:
            self._refs[b] = 1
        self._peak_in_use = max(self._peak_in_use, len(self._in_use))
        return blocks

    def share(self, blocks):
        """Grant one additional reference to each of ``blocks``.  Every
        block must already be allocated — sharing a free block would
        resurrect contents the pool no longer guarantees."""
        for b in blocks:
            if b not in self._in_use:
                raise MXNetError(f"cannot share free block {b}")
        for b in blocks:
            self._refs[b] += 1
        self._peak_shared = max(self._peak_shared, self.shared_blocks)

    def release(self, blocks):
        """Drop one reference from each of ``blocks``; a block returns
        to the free list only when its last holder lets go.  Unknown /
        already-free ids raise (the no-double-assignment invariant's
        enforcement edge, unchanged from the pre-refcount ``free``)."""
        for b in blocks:
            if b not in self._in_use:
                raise MXNetError(f"block {b} is not allocated")
        for b in blocks:
            self._refs[b] -= 1
            if self._refs[b] == 0:
                del self._refs[b]
                self._in_use.discard(b)
                self._free.append(b)

    def free(self, blocks):
        """Historical name for :meth:`release` (identical semantics for
        refcount-1 blocks, which is every block before r19)."""
        self.release(blocks)

    def check(self):
        free = set(self._free)
        if len(free) != len(self._free):
            raise MXNetError("duplicate ids on the free list")
        if free & self._in_use:
            raise MXNetError(
                f"blocks both free and in use: {free & self._in_use}")
        if free | self._in_use != set(range(self.num_blocks)):
            raise MXNetError("block pool lost track of blocks")
        if set(self._refs) != self._in_use:
            raise MXNetError("refcount table does not match the in-use "
                             "set")
        bad = [b for b, c in self._refs.items() if c < 1]
        if bad:
            raise MXNetError(f"allocated blocks with refcount < 1: {bad}")
        return True


class PagedKVCacheManager:
    """Block-pool ledger: slots are still the decode batch rows (the
    step program's shape), but K/V capacity comes from a shared
    :class:`BlockAllocator`.  A request holds the blocks its written
    tokens need: its prompt's at admission (:meth:`admit`), then the
    block each next write lands in, granted as the step that writes is
    queued (:meth:`grant_step`).  Its maximum (``ceil((prompt + budget)
    / block_size)`` blocks, :meth:`blocks_for`) is a claim: a request is
    admitted, and a block granted, only if every admitted request can
    still reach its maximum afterwards, one after another
    (:meth:`_safe`).  A slot whose grant is refused is left out of that
    step and asked about again at the next: nothing is evicted.  All
    transitions are lock-serialized: the prefill lane admits while the
    decode lane grants, advances and evicts."""

    def __init__(self, num_slots, max_len, num_blocks, block_size,
                 kv_bytes_per_block=0, state_bytes_per_slot=0):
        if num_slots < 1:
            raise MXNetError("num_slots must be >= 1")
        self.num_slots = int(num_slots)
        self.max_len = int(max_len)
        self.block_size = int(block_size)
        #: bytes behind the two kinds of state this ledger counts
        #: (``CacheSpec``): a block over the K/V layers only, and the
        #: fixed per-slot state of the layers that keep one (0: none)
        self.kv_bytes_per_block = int(kv_bytes_per_block)
        self.state_bytes_per_slot = int(state_bytes_per_slot)
        self.allocator = BlockAllocator(num_blocks, block_size)
        self.num_blocks = self.allocator.num_blocks
        #: static per-slot block-table width: the step program gathers
        #: this many blocks per slot whatever the request actually owns
        self.max_blocks = -(-self.max_len // self.block_size)
        self._free = list(range(self.num_slots - 1, -1, -1))
        self._active = {}
        self._admits = 0
        self._evictions = 0
        self._peak_occupancy = 0
        self._peak_tokens = 0
        #: blocks the active requests may still ask for: the sum of
        #: their maxima less what they hold
        self._owed = 0
        #: blocks granted behind a cursor, slots left out of a step for
        #: want of one (one a slot a step: each a grant the safe-state
        #: rule refused), and the admissions the rule refused
        self._grants = 0
        self._parked_slot_ticks = 0
        self._refused_admits = 0
        self._lock = threading.RLock()
        #: optional :class:`~mxnet_tpu.serving.radix.RadixPrefixCache`
        #: holding its own references on cached prefix blocks; consulted
        #: by ``check()`` so the refcount invariant covers cache-held
        #: blocks too.
        self.prefix_cache = None

    # -- queries --------------------------------------------------------------
    def blocks_for(self, prompt_len, max_new_tokens):
        """The most blocks a request can come to hold (prompt rows +
        every decode write): its claim on the pool from admission on,
        which the safe-state rule keeps within reach."""
        return -(-(prompt_len + max_new_tokens) // self.block_size)

    def _claim(self, st):
        return -(-st.reserved // self.block_size)

    def admission(self):
        """The manager's lock, for the prefill lane to hold from its
        gate (:meth:`admissible`) to its admits: growth asks for the
        same lock, so no grant lands between what the gate found safe
        and the admission."""
        return self._lock

    def _entry(self, prompt_len, max_new_tokens, shared):
        """A request not yet admitted, as the rule sees it -> (fresh
        blocks its prompt takes now, blocks it may still ask for, blocks
        that return when it ends).  ``shared``: the leading blocks it
        would share and not allocate."""
        held = -(-prompt_len // self.block_size)
        # under a prefix cache every whole block of the prompt may
        # outlive the request (the cache takes its reference after the
        # commit): only the blocks behind them are counted on
        kept = prompt_len // self.block_size \
            if self.prefix_cache is not None else shared
        return (held - shared,
                self.blocks_for(prompt_len, max_new_tokens) - held,
                held - kept)

    def _safe(self, taken=0, moved=None, extra=()):
        """The safe-state rule (the banker's, for one kind of resource):
        can the admitted requests be put in an order in which each
        one's remaining need (maximum less held) fits in the free
        blocks plus everything released by those before it?  Asked of
        the state as it would be once ``taken`` more blocks have left
        the free list: to ``moved`` (a :class:`SlotState`: a grant), or
        as the prompts of requests not yet admitted, ``extra``
        (``(owed, released)`` each: an admission).

        One comparison while every remaining need fits at once, which
        is what a reservation at admission used to hold back: a pool of
        ``num_slots x max_blocks`` never gets past it.  Else one sort
        by remaining need and one pass: the first request that does not
        fit has every later one behind it."""
        free = self.allocator.free_blocks - taken
        owed = self._owed + sum(e[0] for e in extra)
        if moved is not None:
            owed -= taken
        if free < 0:
            return False
        if owed <= free:
            return True
        rows = list(extra)
        for st in self._active.values():
            n = taken if st is moved else 0
            held = len(st.blocks) + n
            rows.append((self._claim(st) - held, held - st.kept))
        for need, released in sorted(rows):
            if need > free:
                return False
            free += released
        return True

    def admissible(self, requests):
        """Whether the state with every one of ``requests`` admitted
        (``(prompt_len, max_new_tokens, shared blocks)`` each) is safe
        and has the slots: the prefill lane's gate for a batch, asked
        under :meth:`admission`."""
        with self._lock:
            if len(requests) > len(self._free):
                return False
            entries = [self._entry(*q) for q in requests]
            ok = self._safe(sum(e[0] for e in entries),
                            extra=[e[1:] for e in entries])
            if not ok:
                self._refused_admits += 1
            return ok

    def free_slots(self):
        with self._lock:
            return len(self._free)

    def active_slots(self):
        with self._lock:
            return sorted(self._active)

    def state(self, slot):
        return self._active[slot]

    def tokens_in_flight(self):
        with self._lock:
            return sum(st.pos for st in self._active.values())

    def _holders(self):
        """block id -> number of active block lists containing it
        (callers hold the lock)."""
        holders = {}
        for st in self._active.values():
            for b in st.blocks:
                holders[b] = holders.get(b, 0) + 1
        return holders

    def reserved_tokens(self):
        """Token budget of the active requests (their claims, not what
        they hold), counting each shared prefix block's capacity ONCE —
        the pool only spends one block however many requests read
        it."""
        with self._lock:
            total = sum(st.reserved for st in self._active.values())
            over = sum((c - 1) * self.block_size
                       for c in self._holders().values() if c > 1)
            return total - over

    def stats(self):
        """Slot counters plus pool metrics.  ``fragmentation`` here is
        *internal*: the fraction of allocated block capacity not yet
        holding a live token (the tail of each request's last block).
        ``grants``: blocks granted behind a cursor; ``parked_slot_ticks``:
        slots left out of a step for want of a block, one a slot a
        step; ``unsafe_refusals``: looks of the safe-state rule that
        refused, at the prefill lane's gate and at a grant."""
        with self._lock:
            live = sum(st.pos for st in self._active.values())
            # shared prefix blocks store their rows ONCE however many
            # slots read them: subtract the duplicate holders' share so
            # utilization / fragmentation describe physical rows.
            over = sum((c - 1) * self.block_size
                       for c in self._holders().values() if c > 1)
            live_unique = live - over
            used = self.allocator.blocks_in_use
            alloc_cap = used * self.block_size
            cap = self.num_blocks * self.block_size
            return {
                "admits": self._admits, "evictions": self._evictions,
                "occupancy": len(self._active),
                "peak_occupancy": self._peak_occupancy,
                "num_slots": self.num_slots,
                "num_blocks": self.num_blocks,
                "block_size": self.block_size,
                "blocks_in_use": used,
                "peak_blocks_in_use": self.allocator.peak_blocks_in_use,
                "shared_blocks": self.allocator.shared_blocks,
                "peak_shared_blocks": self.allocator.peak_shared_blocks,
                "capacity_tokens": cap,
                "kv_block_bytes_in_use": used * self.kv_bytes_per_block,
                "state_bytes_per_slot": self.state_bytes_per_slot,
                "state_bytes_in_use":
                    len(self._active) * self.state_bytes_per_slot,
                "tokens_in_flight": int(live_unique),
                "reserved_tokens": int(self.reserved_tokens()),
                "peak_tokens": int(self._peak_tokens),
                "utilization": round(live_unique / cap, 4) if cap
                else 0.0,
                "fragmentation": round(1.0 - live_unique / alloc_cap, 4)
                if alloc_cap else 0.0,
                **self.growth(),
            }

    def growth(self):
        """The counters of growth on demand (see :meth:`stats`)."""
        with self._lock:
            return {"grants": self._grants,
                    "parked_slot_ticks": self._parked_slot_ticks,
                    "unsafe_refusals": {
                        "admit": self._refused_admits,
                        "grant": self._parked_slot_ticks}}

    # -- transitions ----------------------------------------------------------
    def admit(self, request_id, prompt_len, max_new_tokens, step=0,
              shared_blocks=None):
        """Claim a slot AND the prompt's blocks, and put the request's
        maximum on the books.  Returns ``(slot, blocks)``, or None when
        no slot is free or the state with the request admitted would
        not be safe (the request stays queued).

        ``shared_blocks`` (r19): already-allocated prefix blocks the
        request will read instead of prefilling — the radix cache's
        lookup result, in logical order, covering whole leading blocks
        of the prompt.  They are ``share()``d (the request's own
        reference) and only the rest of the prompt's blocks is freshly
        allocated; on admit failure no references are taken."""
        if prompt_len + max_new_tokens > self.max_len:
            raise MXNetError(
                f"sequence budget {prompt_len}+{max_new_tokens} exceeds "
                f"cache max_len {self.max_len}")
        shared = list(shared_blocks) if shared_blocks else []
        if len(shared) > self.blocks_for(prompt_len, max_new_tokens):
            raise MXNetError(
                f"{len(shared)} shared prefix blocks exceed the "
                f"request's {self.blocks_for(prompt_len, max_new_tokens)}"
                "-block budget")
        with self._lock:
            if not self.admissible([(prompt_len, max_new_tokens,
                                     len(shared))]):
                return None
            need, owed, released = self._entry(prompt_len, max_new_tokens,
                                               len(shared))
            fresh = self.allocator.alloc(need)
            if shared:
                self.allocator.share(shared)
            blocks = shared + fresh
            slot = self._free.pop()
            self._active[slot] = SlotState(
                request_id, prompt_len, max_new_tokens, step,
                blocks=blocks, reserved=prompt_len + max_new_tokens,
                kept=len(blocks) - released)
            self._owed += owed
            self._admits += 1
            self._peak_occupancy = max(self._peak_occupancy,
                                       len(self._active))
            self._peak_tokens = max(
                self._peak_tokens,
                sum(st.pos for st in self._active.values()))
            return slot, blocks

    def grant_step(self, slots, n=1):
        """Before a step is queued: each of ``slots``, oldest admission
        first, is granted the blocks that its next ``n`` writes land in
        and it does not hold (never past its maximum), if the state
        after the grant is safe.  Returns ``(grants, parked)``:
        ``{slot: (index in its list of the first new block, the new
        blocks)}`` for the engine's tables, and the slots refused, which
        the step leaves out and the next one asks about again, cursor,
        count and blocks untouched."""
        wanted = set(slots)
        bs = self.block_size
        grants, parked = {}, []
        with self._lock:
            for slot, st in self._active.items():   # in order of admission
                if slot not in wanted:
                    continue
                short = -(-min(st.pos + n, st.reserved) // bs) \
                    - len(st.blocks)
                if short <= 0:
                    continue
                if not self._safe(short, moved=st):
                    parked.append(slot)
                    continue
                grants[slot] = (len(st.blocks), self.allocator.alloc(short))
                # a new list: the one admit() returned is the prefill
                # lane's to read
                st.blocks = st.blocks + grants[slot][1]
                self._owed -= short
                self._grants += short
            self._parked_slot_ticks += len(parked)
        return grants, parked

    def _moved(self, slot, st):
        """The cursor moved on: it stays inside the budget and the
        blocks held."""
        if st.pos > st.reserved:
            raise MXNetError(
                f"slot {slot} overran its reserved {st.reserved} "
                "tokens")
        if st.pos > len(st.blocks) * self.block_size:
            raise MXNetError(
                f"slot {slot} wrote past the {len(st.blocks)} blocks it "
                "holds (no grant_step before the step)")
        self._peak_tokens = max(
            self._peak_tokens,
            sum(s.pos for s in self._active.values()))

    def advance(self, slot):
        with self._lock:
            st = self._active[slot]
            st.pos += 1
            self._moved(slot, st)

    def advance_n(self, slot, n):
        """``n`` decode/verify writes landed for ``slot`` in one
        dispatch (the k-token verify forward): bump the cursor by ``n``.
        The caller rolls back any rejected suffix with
        :meth:`truncate`."""
        if n < 0:
            raise MXNetError(f"cannot advance by {n}")
        with self._lock:
            st = self._active[slot]
            st.pos += int(n)
            self._moved(slot, st)

    def truncate(self, slot, pos):
        """Roll ``slot``'s write cursor back to ``pos`` (speculative
        rejection, or an early stop releasing unused budget).  The
        budget shrinks to what the sequence can still need (``pos +
        remaining``), and with it the claim; whole blocks past the new
        cursor return to the pool (the next step's grant asks for them
        again); returns the released block ids.

        No device-side cleanup happens: rejected rows in a block that
        stays sit beyond the causal mask (``t <= pos``) until the next
        verify/decode write overwrites them — the same stale-row
        invariant that lets a fresh block skip zeroing.  The caller
        takes released blocks out of the engine's tables."""
        with self._lock:
            st = self._active[slot]
            if not 0 <= pos <= st.pos:
                raise MXNetError(
                    f"truncate target {pos} outside [0, {st.pos}] for "
                    f"slot {slot}")
            owed = self._claim(st) - len(st.blocks)
            st.pos = int(pos)
            st.reserved = min(st.reserved,
                              st.pos + max(int(st.remaining), 0))
            keep = -(-st.pos // self.block_size)
            released = st.blocks[keep:]
            if released:
                st.blocks = st.blocks[:keep]
                st.kept = min(st.kept, keep)
                self.allocator.release(released)
            self._owed += self._claim(st) - len(st.blocks) - owed
            return released

    def consume(self, slot):
        with self._lock:
            st = self._active[slot]
            st.remaining -= 1
            return st.remaining <= 0

    def evict(self, slot):
        """Release the slot and drop the request's reference on every
        block it held; blocks shared with the radix cache or another
        request stay allocated for the remaining holders."""
        with self._lock:
            if slot not in self._active:
                raise MXNetError(f"slot {slot} is not active")
            st = self._active.pop(slot)
            self._owed -= self._claim(st) - len(st.blocks)
            self.allocator.release(st.blocks)
            self._free.append(slot)
            self._evictions += 1
            return st.blocks

    def check(self):
        """Slot invariants + block invariants.  A request's blocks
        cover its cursor, which moves as a step is queued and so covers
        the writes in flight, and are never more than its maximum; the
        books of what is still owed add up and the state is safe.
        Since r19 block lists
        may overlap on shared prefix blocks, so the partition check
        becomes a refcount check: every allocated block's holder count
        must equal the number of active block lists containing it plus
        one if the radix prefix cache holds it, and the union of all
        holders must cover the allocator's in-use set exactly."""
        with self._lock:
            free = set(self._free)
            active = set(self._active)
            if free & active:
                raise MXNetError(
                    f"slots both free and active: {free & active}")
            if free | active != set(range(self.num_slots)):
                raise MXNetError("slot ledger lost track of slots")
            for slot, st in self._active.items():
                if not 0 <= st.pos <= st.reserved <= self.max_len:
                    raise MXNetError(
                        f"slot {slot} pos {st.pos} / reserved "
                        f"{st.reserved} out of range")
                if len(st.blocks) * self.block_size < st.pos:
                    raise MXNetError(
                        f"slot {slot} blocks cover "
                        f"{len(st.blocks) * self.block_size} < its cursor "
                        f"{st.pos}")
                if not st.kept <= len(st.blocks) <= self._claim(st):
                    raise MXNetError(
                        f"slot {slot} holds {len(st.blocks)} blocks "
                        f"outside [{st.kept} it shares, its maximum "
                        f"{self._claim(st)}]")
                if len(st.blocks) != len(set(st.blocks)):
                    raise MXNetError(
                        f"slot {slot} lists a block twice")
            owed = sum(self._claim(st) - len(st.blocks)
                       for st in self._active.values())
            if owed != self._owed:
                raise MXNetError(
                    f"{self._owed} blocks on the books as still owed, "
                    f"{owed} by the requests' own")
            if not self._safe():
                raise MXNetError(
                    "unsafe state: no order lets every admitted request "
                    "reach its maximum")
            holders = self._holders()
            cached = (self.prefix_cache.block_refs()
                      if self.prefix_cache is not None else {})
            union = set(holders) | set(cached)
            if union != self.allocator._in_use:
                raise MXNetError(
                    "active block lists + cached prefixes do not match "
                    "the allocator's in-use set")
            for b in union:
                want = holders.get(b, 0) + cached.get(b, 0)
                have = self.allocator.refcount(b)
                if have != want:
                    raise MXNetError(
                        f"block {b} refcount {have} != {want} holders "
                        f"({holders.get(b, 0)} slots + "
                        f"{cached.get(b, 0)} cached)")
            self.allocator.check()
            return True

"""Blocks granted as a request grows (``serving/kv_cache.py``
``PagedKVCacheManager``): the manager alone, no device.  A request holds the
blocks its written tokens need and its maximum stays on the books as a claim;
a request is admitted and a block granted only if every admitted request can
still finish afterwards (the safe-state rule); a slot refused is parked for
that step and nothing is evicted."""
import collections
import random

import pytest

import mxnet_tpu as mx
from mxnet_tpu.serving import kv_cache
from mxnet_tpu.serving.kv_cache import PagedKVCacheManager
from mxnet_tpu.serving.radix import RadixPrefixCache

BS = 4


def _covered(m, slot, n):
    """The invariant, restated: the blocks cover the cursor and the next
    ``n`` writes (never past the budget), and are never more than the
    maximum."""
    st = m.state(slot)
    assert len(st.blocks) * BS >= min(st.pos + n, st.reserved)
    assert len(st.blocks) * BS >= st.pos
    assert len(st.blocks) <= -(-st.reserved // BS)
    return m.check()


# --- admission and growth ------------------------------------------------------

def test_admission_takes_the_prompts_blocks_and_books_the_maximum():
    m = PagedKVCacheManager(num_slots=2, max_len=64, num_blocks=16,
                            block_size=BS)
    slot, blocks = m.admit("a", prompt_len=9, max_new_tokens=20)
    st = m.state(slot)
    assert len(blocks) == 3 == len(st.blocks)       # ceil(9 / 4)
    assert m.blocks_for(9, 20) == 8 == m._claim(st)
    assert st.reserved == 29 and m._owed == 5
    assert m.allocator.blocks_in_use == 3
    # the token budget is what the router weighs, not the blocks held
    assert m.reserved_tokens() == 29
    assert m.stats()["grants"] == 0
    m.check()
    m.evict(slot)
    assert m._owed == 0 and m.allocator.blocks_in_use == 0
    m.check()


@pytest.mark.parametrize("n", [1, 4, 3], ids=["token", "block_len", "verify_k"])
def test_grants_follow_the_cursor(n):
    """A step of one token, a block decoder's pass of ``block_len`` and a
    speculative window of ``k`` + 1 rows: before each, the blocks the writes
    land in; never one ahead of them, never past the maximum."""
    m = PagedKVCacheManager(num_slots=2, max_len=64, num_blocks=32,
                            block_size=BS)
    slot, _ = m.admit("a", prompt_len=6, max_new_tokens=21)
    st = m.state(slot)
    granted = 0
    while st.pos < st.reserved:
        before = len(st.blocks)
        grants, parked = m.grant_step([slot], n)
        assert not parked
        want = -(-min(st.pos + n, st.reserved) // BS)
        assert len(st.blocks) == max(before, want)
        if slot in grants:
            at, fresh = grants[slot]
            assert at == before and st.blocks[at:] == fresh
            granted += len(fresh)
        _covered(m, slot, n)
        m.advance_n(slot, min(n, st.reserved - st.pos))
        _covered(m, slot, 0)
    assert len(st.blocks) == m.blocks_for(6, 21) == 7
    assert m.stats()["grants"] == granted == 7 - 2
    assert m._owed == 0
    assert m.grant_step([slot], n) == ({}, [])      # nothing past the maximum


def test_truncate_returns_what_a_rollback_frees():
    m = PagedKVCacheManager(num_slots=1, max_len=64, num_blocks=16,
                            block_size=BS)
    slot, _ = m.admit("a", prompt_len=7, max_new_tokens=20)
    st = m.state(slot)
    m.grant_step([slot], 6)             # rows 7..12: blocks 1, 2 and 3
    assert len(st.blocks) == 4
    m.advance_n(slot, 6)
    m.check()
    for _ in range(2):
        m.consume(slot)                 # two of the six were accepted
    freed = m.truncate(slot, 9)         # rows 9..12 rejected
    # rows 0..8 lie in three blocks: the fourth held rejected rows only
    assert len(freed) == 1 and len(st.blocks) == 3
    assert st.pos == 9 and st.reserved == 27 and m._claim(st) == 7
    assert m._owed == 4 and m.allocator.free_blocks == 13
    m.check()
    # the next window asks for it again
    grants, _ = m.grant_step([slot], 6)
    assert grants[slot][0] == 3 and len(st.blocks) == 4
    assert m.truncate(slot, 9) and m.truncate(slot, 9) == []
    m.check()


def test_a_write_without_its_grant_is_refused():
    m = PagedKVCacheManager(num_slots=1, max_len=32, num_blocks=8,
                            block_size=BS)
    slot, _ = m.admit("a", prompt_len=8, max_new_tokens=8)
    with pytest.raises(mx.MXNetError, match="wrote past the 2 blocks"):
        m.advance(slot)


# --- the safe-state rule --------------------------------------------------------

def _pair(num_blocks=8):
    """Two requests of 2 prompt blocks and a maximum of 6 in a pool of 8: either
    can finish alone (4 more of 4 free), both cannot at once."""
    m = PagedKVCacheManager(num_slots=3, max_len=32, num_blocks=num_blocks,
                            block_size=BS)
    a, _ = m.admit("a", prompt_len=8, max_new_tokens=16)
    b, _ = m.admit("b", prompt_len=8, max_new_tokens=16)
    return m, a, b


def test_a_refused_grant_parks_and_changes_nothing():
    m, a, b = _pair()
    # a's third block leaves 3 free with a owing 3: a can finish, then b.
    # b's would leave 2 free with each owing 3: nobody could
    grants, parked = m.grant_step([a, b])
    assert list(grants) == [a] and parked == [b]
    sa, sb = m.state(a), m.state(b)
    assert (len(sb.blocks), sb.pos, sb.remaining) == (2, 8, 16)
    st = m.stats()
    assert st["grants"] == 1 and st["parked_slot_ticks"] == 1
    assert st["unsafe_refusals"] == {"admit": 0, "grant": 1}
    m.check()
    # parked at every step while a goes on to its maximum ...
    for _ in range(16):
        grants, parked = m.grant_step([a, b])
        assert parked == [b] and set(grants) <= {a}
        m.advance(a)
        m.check()
    assert len(sa.blocks) == 6 and len(sb.blocks) == 2
    assert m.stats()["parked_slot_ticks"] == 17
    # ... and back in once a has finished: nothing was evicted for room
    m.evict(a)
    for _ in range(16):
        grants, parked = m.grant_step([b])
        assert not parked
        m.advance(b)
    assert len(sb.blocks) == 6 and m.stats()["evictions"] == 1
    m.check()


def test_grants_are_tried_oldest_admission_first():
    """One block free and two slots that each could take it safely alone:
    the one admitted first gets it, whatever its slot number."""
    m = PagedKVCacheManager(num_slots=3, max_len=32, num_blocks=7,
                            block_size=BS)
    x, _ = m.admit("x", prompt_len=8, max_new_tokens=1)     # takes slot 0
    old, _ = m.admit("old", prompt_len=8, max_new_tokens=4)
    m.evict(x)
    new, _ = m.admit("new", prompt_len=8, max_new_tokens=4)  # slot 0 again
    assert new < old
    filler, _ = m.admit("f", prompt_len=8, max_new_tokens=0)
    assert m.allocator.free_blocks == 1
    grants, parked = m.grant_step([new, old, filler])
    assert list(grants) == [old] and parked == [new]
    m.check()


def test_the_head_is_admitted_only_into_a_safe_state():
    m, a, b = _pair()
    # a third of the same kind: its prompt fits (4 free) but then nobody
    # could reach a maximum of 6 with 2 free and 4 owed each
    assert not m.admissible([(8, 16, 0)])
    assert m.admit("c", prompt_len=8, max_new_tokens=16) is None
    assert m.stats()["unsafe_refusals"]["admit"] == 2
    assert m.free_slots() == 1 and m.allocator.free_blocks == 4
    # a short one is: 2 more blocks at most, then it gives all 4 back
    assert m.admissible([(8, 8, 0)])
    # and the gate asks about a batch as a whole
    assert not m.admissible([(8, 8, 0), (8, 8, 0)])         # one slot free
    m.evict(b)
    assert m.admissible([(8, 8, 0), (4, 4, 0)])
    assert not m.admissible([(8, 16, 0), (8, 16, 0)])
    m.check()


def test_a_shared_prefix_is_not_counted_on():
    """Under a prefix cache a prompt's whole blocks may outlive the request
    (the cache takes its reference after the commit): the rule counts only on
    the blocks behind them."""
    plain = PagedKVCacheManager(num_slots=2, max_len=32, num_blocks=8,
                                block_size=BS)
    m = PagedKVCacheManager(num_slots=2, max_len=32, num_blocks=8,
                            block_size=BS)
    rx = RadixPrefixCache(m.allocator, block_size=BS, capacity_tokens=32)
    m.prefix_cache = rx
    prompt = list(range(9))
    plain.admit("a", prompt_len=9, max_new_tokens=11)
    a, blocks = m.admit("a", prompt_len=9, max_new_tokens=11)
    assert plain.state(0).kept == 0 and m.state(a).kept == 2
    # a newcomer that would hold 1 and owe 6 with 4 left free: it can finish
    # once a has (2 more, then 3 back), not if a gives back only 1
    assert plain.admissible([(4, 24, 0)])
    assert not m.admissible([(4, 24, 0)])
    rx.insert(prompt, blocks)
    m.check()
    _matched, shared = rx.lookup(prompt)
    b, blocks_b = m.admit("b", prompt_len=9, max_new_tokens=11,
                          shared_blocks=shared)
    assert blocks_b[:2] == blocks[:2] and m.allocator.blocks_in_use == 4
    assert m.state(b).kept == 2 and m._owed == 4
    m.check()
    for s in (a, b):
        m.grant_step([s], 11)
        m.advance_n(s, 11)
        m.check()
        m.evict(s)
    assert m.allocator.blocks_in_use == 2       # the cache's own
    m.check()


@pytest.mark.parametrize("tamper, says", [
    (lambda m, st: setattr(st, "pos", st.pos + 5), "blocks cover"),
    (lambda m, st: st.blocks.extend(m.allocator.alloc(9)), "its maximum"),
    (lambda m, st: setattr(m, "_owed", m._owed + 1), "on the books"),
], ids=["behind_the_cursor", "past_the_maximum", "books"])
def test_check_restates_the_invariant(tamper, says):
    m = PagedKVCacheManager(num_slots=2, max_len=64, num_blocks=32,
                            block_size=BS)
    slot, _ = m.admit("a", prompt_len=8, max_new_tokens=24)
    m.check()
    tamper(m, m.state(slot))
    with pytest.raises(mx.MXNetError, match=says):
        m.check()


def test_check_refuses_an_unsafe_state():
    m, a, b = _pair()
    for s in (a, b):
        st = m.state(s)
        st.blocks = st.blocks + m.allocator.alloc(2)    # past the rule
        m._owed -= 2
    # 0 free, each owes 2
    with pytest.raises(mx.MXNetError, match="unsafe state"):
        m.check()


# --- every admitted request finishes --------------------------------------------

def _requests(seed, n=40, max_len=96):
    rnd = random.Random(seed)
    out = []
    for i in range(n):
        p = rnd.randint(1, 40)
        out.append((f"r{i}", p, rnd.randint(1, max_len - p)))
    return out


def _drive(m, reqs, n=1):
    """A closed loop over the manager alone: FIFO admission through the gate,
    then a step of ``n`` writes a slot -> ([(tick, request)] admitted, ticks).
    ``check()`` (which holds the state to the rule) at every transition, and a
    step that steps nobody with slots held is a deadlock."""
    queue = collections.deque(reqs)
    live, admitted, tick = {}, [], 0
    while queue or live:
        tick += 1
        assert tick < 20_000
        while queue and m.free_slots():
            rid, p, new = queue[0]
            if not m.admissible([(p, new, 0)]):
                break
            slot, blocks = m.admit(rid, p, new)
            assert len(blocks) == -(-p // m.block_size)
            queue.popleft()
            admitted.append((tick, rid))
            live[slot] = rid
            m.check()
        active = sorted(live)
        grants, parked = m.grant_step(active, n)
        m.check()                       # no grant left an unsafe state
        stepped = [s for s in active if s not in parked]
        assert stepped or not active, "every slot parked: a deadlock"
        for s in stepped:
            st = m.state(s)
            adv = min(n, st.reserved - st.pos)
            m.advance_n(s, adv)
            if any([m.consume(s) for _ in range(adv)]):
                m.evict(s)
                del live[s]
        m.check()
    return admitted, tick


def _reserving(reqs, slots, num_blocks, bs, n=1):
    """The rule this replaced, by itself: the whole maximum taken at
    admission, the FIFO head admitted when a slot and that many blocks are
    free -> [(tick, request)] admitted."""
    queue = collections.deque(reqs)
    live, admitted, tick, free = {}, [], 0, num_blocks
    while queue or live:
        tick += 1
        while queue and len(live) < slots:
            rid, p, new = queue[0]
            need = -(-(p + new) // bs)
            if need > free:
                break
            queue.popleft()
            free -= need
            admitted.append((tick, rid))
            live[rid] = [new, need]
        for rid in list(live):
            live[rid][0] -= n
            if live[rid][0] <= 0:
                free += live.pop(rid)[1]
    return admitted


@pytest.mark.parametrize("n", [1, 4], ids=["token", "block_len"])
@pytest.mark.parametrize("level", [0.0, 0.3, 0.6, 1.0],
                         ids=["largest_maximum", "x0.3", "x0.6", "parity"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_every_admitted_request_finishes(seed, level, n, monkeypatch):
    """Seeded random lengths through pools from one largest maximum to parity
    (slots x max_blocks): no deadlock, no unsafe state, every block back.  At
    parity the admissions are the reservation rule's tick for tick, nothing
    parks, nothing is refused and the rule never sorts."""
    slots, max_len = 6, 96
    reqs = _requests(seed, max_len=max_len)
    floor = max(-(-(p + new) // BS) for _r, p, new in reqs)
    parity = slots * -(-max_len // BS)
    num_blocks = round(floor + level * (parity - floor))
    sorts = []
    monkeypatch.setattr(kv_cache, "sorted",
                        lambda rows: sorts.append(1) or sorted(rows),
                        raising=False)
    m = PagedKVCacheManager(slots, max_len, num_blocks, BS)
    admitted, ticks = _drive(m, reqs, n)
    assert [rid for _t, rid in admitted] == [r[0] for r in reqs]     # FIFO, all
    st = m.stats()
    assert st["admits"] == st["evictions"] == len(reqs)
    assert st["blocks_in_use"] == 0 and m._owed == 0
    assert st["peak_blocks_in_use"] <= num_blocks
    want = _reserving(reqs, slots, num_blocks, BS, n)
    if level == 1.0:
        assert admitted == want
        assert st["parked_slot_ticks"] == 0
        assert st["unsafe_refusals"] == {"admit": 0, "grant": 0}
        assert not sorts
    elif level == 0.0:
        # one request's maximum of room: the rule sorted, and parked
        assert st["parked_slot_ticks"] > 0 and sorts

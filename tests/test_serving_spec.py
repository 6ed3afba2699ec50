"""r19 speed multipliers: speculative decoding + radix prefix cache.

Three layers of proof:

* **Ledger units** — the ``BlockAllocator`` refcount surface
  (alloc/share/release, free-at-zero, check invariants), the paged
  manager's ``advance_n``/``truncate`` rollback contract (blocks past
  the shrunk reservation return to the pool), and the radix trie
  (block-aligned matching, LRU leaf eviction, evict-while-shared
  keeping the block alive for the remaining holder).
* **Token-exactness** — the server with speculation on (same-net draft
  at several k, and a differently-initialized draft forcing
  mid-sequence rejections) and with the radix cache on must emit
  BIT-identical sequences to the offline ``generate()`` oracle: the
  speed multipliers may never change tokens.
* **Compile discipline** — a dp2 CPU-mesh run with both features on
  stays clean under the retrace sanitizer after one warm pass, and the
  target engine holds exactly one decode-path signature per mode
  (``("verify",)`` in spec mode — never ``("step",)``).
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, serving, telemetry
from mxnet_tpu.serving import ServerConfig
from mxnet_tpu.serving.kv_cache import BlockAllocator, PagedKVCacheManager
from mxnet_tpu.serving.radix import RadixPrefixCache
from mxnet_tpu.telemetry.sinks import ListSink


# --- allocator refcounts -----------------------------------------------------

def test_allocator_share_release_refcounts():
    a = BlockAllocator(8, 4)
    blocks = a.alloc(2)
    assert [a.refcount(b) for b in blocks] == [1, 1]
    a.share([blocks[0]])
    assert a.refcount(blocks[0]) == 2
    assert a.shared_blocks == 1
    a.check()
    # first release drops to 1 holder — the block stays allocated
    a.release([blocks[0]])
    assert a.refcount(blocks[0]) == 1
    assert a.blocks_in_use == 2 and a.shared_blocks == 0
    # last release frees it
    a.release(blocks)
    assert a.blocks_in_use == 0 and a.free_blocks == 8
    assert a.peak_shared_blocks == 1
    a.check()


def test_allocator_share_free_block_rejected():
    a = BlockAllocator(4, 4)
    blocks = a.alloc(1)
    a.free(blocks)
    with pytest.raises(mx.MXNetError):
        a.share(blocks)                    # resurrecting a freed block
    with pytest.raises(mx.MXNetError):
        a.release(blocks)                  # double free still rejected


# --- truncate rollback -------------------------------------------------------

def test_paged_truncate_releases_tail_blocks():
    m = PagedKVCacheManager(num_slots=2, max_len=64, num_blocks=16,
                            block_size=4)
    slot, blocks = m.admit("r1", prompt_len=10, max_new_tokens=20)
    st = m.state(slot)
    st.pos = 10                            # prefill wrote the prompt
    assert st.reserved == 30 and len(blocks) == 3   # of 8 at most
    # a window of 5 writes: rows 10..14 end in the fourth block
    grants, parked = m.grant_step([slot], 5)
    assert grants == {slot: (3, st.blocks[3:])} and not parked
    assert len(st.blocks) == 4
    m.advance_n(slot, 5)
    for _ in range(5):
        m.consume(slot)
    # 15 tokens remain owed; rolling back to pos 12 shrinks the budget
    # to 12 + 15 = 27 tokens, the claim to 7 blocks, and the block that
    # held rejected rows only (12..14 are in the fourth) goes back
    freed = m.truncate(slot, 12)
    assert len(freed) == 1
    assert st.pos == 12 and st.reserved == 27 and len(st.blocks) == 3
    assert m._claim(st) == 7
    assert m.allocator.free_blocks == 13
    m.check()
    with pytest.raises(mx.MXNetError):
        m.truncate(slot, 13)               # cannot truncate forward
    m.evict(slot)
    assert m.allocator.blocks_in_use == 0


def test_paged_advance_n_respects_reservation():
    m = PagedKVCacheManager(num_slots=1, max_len=32, num_blocks=8,
                            block_size=4)
    slot, _ = m.admit("r1", prompt_len=4, max_new_tokens=4)
    m.state(slot).pos = 4
    with pytest.raises(mx.MXNetError, match="wrote past the 1 blocks"):
        m.advance_n(slot, 4)               # no grant before the writes
    m.state(slot).pos = 4
    m.grant_step([slot], 4)
    m.advance_n(slot, 4)                   # up to reserved is fine
    with pytest.raises(mx.MXNetError):
        m.advance_n(slot, 1)               # past the reservation raises


# --- radix trie --------------------------------------------------------------

def test_radix_insert_lookup_block_aligned():
    a = BlockAllocator(8, 4)
    rx = RadixPrefixCache(a, block_size=4, capacity_tokens=64)
    blocks = a.alloc(3)
    prompt = list(range(11))               # cap: 10 // 4 * 4 = 8 tokens
    rx.insert(prompt, blocks)
    assert rx.cached_tokens() == 8         # only FULL blocks cached
    assert a.refcount(blocks[0]) == 2      # cache holds its own ref
    assert a.refcount(blocks[2]) == 1      # partial tail block: not cached
    matched, shared = rx.lookup(prompt)
    assert matched == 8 and shared == blocks[:2]
    # a prompt that IS exactly the cached prefix must leave >= 1 novel
    # token: the match caps at (len - 1) // bs * bs
    assert rx.match_len(prompt[:8]) == 4
    # diverging second block: only the first matches
    other = prompt[:4] + [99] * 7
    assert rx.match_len(other) == 4
    rx.clear()
    assert a.refcount(blocks[0]) == 1
    a.free(blocks)
    a.check()


def test_radix_lru_eviction_and_evict_while_shared():
    a = BlockAllocator(8, 4)
    rx = RadixPrefixCache(a, block_size=4, capacity_tokens=8)
    b1 = a.alloc(2)
    rx.insert(list(range(9)), b1)          # 2 nodes = 8 tokens (at budget)
    a.release(b1)                          # prefiller done: cache sole holder
    # a "request" adopts the first cached block (evict-while-shared prey)
    a.share([b1[0]])
    b2 = a.alloc(2)
    rx.insert([50 + i for i in range(9)], b2)   # pushes over budget
    a.release(b2)
    assert rx.cached_tokens() == 8 and rx.evictions == 2
    # LRU leaves evicted: the first prompt's path went first, and the
    # shared block SURVIVES in the allocator for its remaining holder
    assert rx.match_len(list(range(9))) == 0
    assert a.refcount(b1[0]) == 1          # cache ref dropped, request's lives
    assert a.refcount(b1[1]) == 0          # unshared leaf fully freed
    a.release([b1[0]])
    rx.clear()
    a.check()
    assert a.blocks_in_use == 0


def test_radix_manager_check_covers_cache_refs():
    m = PagedKVCacheManager(num_slots=2, max_len=32, num_blocks=8,
                            block_size=4)
    rx = RadixPrefixCache(m.allocator, block_size=4, capacity_tokens=32)
    m.prefix_cache = rx
    prompt = list(range(9))
    slot, blocks = m.admit("r1", prompt_len=9, max_new_tokens=4)
    m.state(slot).pos = 9
    rx.insert(prompt, blocks)
    m.check()                              # slot + cache refs reconcile
    # a second request adopts the cached prefix
    matched, shared = rx.lookup(prompt)
    slot2, blocks2 = m.admit("r2", prompt_len=9, max_new_tokens=4,
                             shared_blocks=shared)
    assert blocks2[:2] == blocks[:2]
    assert m.allocator.refcount(blocks[0]) == 3  # 2 slots + cache
    assert m.stats()["shared_blocks"] == 2
    m.check()
    m.evict(slot)
    m.evict(slot2)
    m.check()
    rx.clear()
    assert m.allocator.blocks_in_use == 0


# --- end-to-end token exactness ----------------------------------------------

def _tiny():
    from mxnet_tpu.models.llama import llama_tiny

    net = llama_tiny()
    net.initialize()
    return net


@pytest.mark.parametrize("k", [1, 2, 3])
def test_speculative_token_exact_same_net_draft(k):
    """Same-net draft: every proposal matches, yet the output must be
    byte-identical to plain generate() — the acceptance rule emits only
    target argmaxes."""
    net = _tiny()
    rs = np.random.RandomState(0)
    p1 = rs.randint(1, 250, size=5)
    p2 = rs.randint(1, 250, size=9)
    cfg = ServerConfig(max_batch=2, max_length=64, min_length=8,
                       num_slots=2, summary_every=1 << 30,
                       draft_net=net, spec_k=k)
    srv = serving.GenerativeServer(net, cfg)
    with srv:
        r1 = srv.generate(p1, max_new_tokens=12)
        r2 = srv.generate(p2, max_new_tokens=7)
        stats = srv.stats()
    o1 = net.generate(nd.array(p1[None]), 12).asnumpy()[0]
    o2 = net.generate(nd.array(p2[None]), 7).asnumpy()[0]
    assert np.array_equal(r1, o1)
    assert np.array_equal(r2, o2)
    spec = stats["speculative"]
    assert spec["k"] == k and spec["draft_tokens"] > 0
    # same net -> every in-budget proposal accepted (the only slack is
    # the final round's budget clamp)
    assert spec["accept_rate"] >= 0.6
    sigs = stats["compiled_signatures"]
    assert sigs.count(("verify",)) == 1
    assert ("step",) not in sigs


def test_speculative_token_exact_rejecting_draft():
    """A differently-initialized draft disagrees mid-sequence; rejected
    suffixes roll back through truncate() and the output still matches
    the oracle exactly."""
    net = _tiny()
    draft = _tiny()                        # same arch, different weights
    rs = np.random.RandomState(3)
    prompts = [rs.randint(1, 250, size=n) for n in (5, 9, 12)]
    cfg = ServerConfig(max_batch=2, max_length=64, min_length=8,
                       num_slots=2, summary_every=1 << 30,
                       draft_net=draft, spec_k=3)
    telemetry.enable(memory=False, cost=False)
    sink = ListSink()
    telemetry.add_sink(sink)
    try:
        srv = serving.GenerativeServer(net, cfg)
        with srv:
            outs = [srv.generate(p, max_new_tokens=10) for p in prompts]
            stats = srv.stats()
            srv.replicas[0].mgr.check()
    finally:
        telemetry.disable()
        telemetry.reset()
    for p, r in zip(prompts, outs):
        o = net.generate(nd.array(p[None]), 10).asnumpy()[0]
        assert np.array_equal(r, o)
    spec = stats["speculative"]
    # a random draft over a 256 vocab rejects nearly always — the
    # machinery exercised here IS the rollback path
    assert spec["draft_tokens"] > spec["accepted_tokens"]
    assert stats["kv_cache"]["occupancy"] == 0
    # per-request records carry the speculation telemetry fields
    recs = [r for r in sink.records if r.get("record") == "serving.request"]
    assert recs and all(r["draft_tokens"] > 0 for r in recs)
    assert all("accept_rate" in r for r in recs)


def test_radix_prefix_cache_token_exact_and_shared():
    """Requests sharing a system prompt prefill only their novel
    suffix (prefix KV adopted by reference), with identical tokens."""
    net = _tiny()
    rs = np.random.RandomState(1)
    sys_prompt = rs.randint(1, 250, size=20)
    prompts = [np.concatenate([sys_prompt, rs.randint(1, 250, size=n)])
               for n in (4, 6, 3)]
    cfg = ServerConfig(max_batch=2, max_length=64, min_length=8,
                       num_slots=2, block_size=8, summary_every=1 << 30,
                       radix_cache=True)
    telemetry.enable(memory=False, cost=False)
    sink = ListSink()
    telemetry.add_sink(sink)
    try:
        srv = serving.GenerativeServer(net, cfg)
        with srv:
            outs = [srv.generate(p, max_new_tokens=6) for p in prompts]
            stats = srv.stats()
            srv.replicas[0].mgr.check()
    finally:
        telemetry.disable()
        telemetry.reset()
    for p, r in zip(prompts, outs):
        o = net.generate(nd.array(p[None]), 6).asnumpy()[0]
        assert np.array_equal(r, o)
    rx = stats["radix_cache"]
    assert rx["hits"] >= 2                 # requests 2 and 3 reused
    assert rx["hit_tokens"] >= 2 * 16      # two full 8-token blocks each
    assert stats["kv_cache"]["peak_shared_blocks"] >= 2
    assert stats["kv_cache"]["occupancy"] == 0
    recs = [r for r in sink.records if r.get("record") == "serving.request"]
    hits = [r for r in recs if r.get("prefix_hit_tokens")]
    assert len(hits) >= 2
    assert all(r["prefill_saved_ms"] > 0 for r in hits)


@pytest.mark.parametrize("kv_mode", ["slots", "ledger", None])
def test_server_config_refuses_a_kv_mode_but_paged_by_name(kv_mode):
    with pytest.raises(mx.MXNetError) as exc:
        ServerConfig(kv_mode=kv_mode)
    assert f"kv_mode={kv_mode!r}" in str(exc.value)
    assert "gone" in str(exc.value)


def test_server_config_takes_kv_mode_paged_and_keeps_nothing_of_it():
    cfg = ServerConfig(kv_mode="paged", num_blocks=8)
    assert not hasattr(cfg, "kv_mode")
    assert vars(cfg).keys() == vars(ServerConfig()).keys()


# --- the draft: a paged engine whose table is fixed ----------------------------

def _draft_server():
    net = _tiny()
    cfg = ServerConfig(max_batch=2, max_length=40, min_length=8,
                       num_slots=2, block_size=16, num_blocks=4,
                       summary_every=1 << 30, draft_net=net, spec_k=2)
    return net, serving.GenerativeServer(net, cfg)


def test_the_drafts_pool_is_a_fixed_row_of_blocks_a_slot():
    """Whatever the target's pool, the draft's holds ``max_blocks`` blocks a
    slot (max_len 40 at 16 a block: 3), priced as the planner prices them."""
    from mxnet_tpu.memory import plan_kv_pool

    net, srv = _draft_server()
    rep = srv.replicas[0]
    eng, draft = rep.engine, rep.draft
    assert (eng.num_blocks, draft.num_blocks, draft.max_blocks) == (4, 6, 3)
    assert draft.block_size == eng.block_size == 16
    assert [rep.draft_blocks(s) for s in range(2)] == [[0, 1, 2], [3, 4, 5]]
    cfg = net.config
    assert draft.kv_pool_bytes() == plan_kv_pool(
        cfg.num_layers, cfg.num_kv_heads, cfg.head_dim, num_blocks=6,
        block_size=16)
    assert draft.decode_attention == eng.decode_attention == "gather"
    # vacant until an admission writes a row
    assert (draft._tables == draft.num_blocks).all()


def test_the_drafts_table_rows_are_the_same_lists_admission_after_admission():
    """An admission writes the slot's fixed list, a release the sentinel, a
    second admission of the slot the same list again; the tokens are the
    offline oracle's both times."""
    import time

    net, srv = _draft_server()
    rep = srv.replicas[0]
    draft = rep.draft
    rs = np.random.RandomState(11)
    seen = []
    with srv:
        for n in (5, 9):
            prompt = rs.randint(1, 250, size=n)
            fut = srv.submit(prompt, max_new_tokens=20)
            deadline = time.time() + 60
            held = None
            while held is None and time.time() < deadline:
                with draft.dev_lock:
                    rows = draft._tables.copy()
                for slot in range(2):
                    if rows[slot, 0] != draft.num_blocks:
                        held = (slot, rows[slot].tolist())
            assert held is not None
            assert held[1] == rep.draft_blocks(held[0])
            seen.append(held[0])
            assert np.array_equal(
                fut.result(120),
                net.generate(nd.array(prompt[None]), 20).asnumpy()[0])
            # released: the row is vacant again, the pool's size as it was
            assert (draft._tables == draft.num_blocks).all()
        assert seen[0] == seen[1]          # the freed slot was taken again
    assert draft._pool[0][0].shape[0] == draft.num_blocks == 6


def test_a_draft_is_refused_what_speculation_is():
    """The draft steps a token a slot and is rolled back by its cursor alone:
    a draft whose cache has more than K/V is refused by name."""
    from mxnet_tpu.models import lfm2

    draft = lfm2.lfm2_moe_tiny()
    draft.initialize()
    with pytest.raises(mx.MXNetError, match="rolled back"):
        serving.GenerativeServer(
            _tiny(), ServerConfig(max_batch=2, max_length=64, min_length=8,
                                  num_slots=2, draft_net=draft, spec_k=2))


# --- dp2 mesh, both features, retrace-clean ----------------------------------

def test_dp2_spec_radix_token_exact_sanitizer_clean():
    """Both multipliers on over a dp2 CPU mesh: token-exact on every
    replica, zero post-warmup retraces, one decode-path signature per
    engine, and the refcount invariants hold at drain."""
    import jax
    from jax.sharding import Mesh
    from mxnet_tpu.telemetry import retrace

    if len(jax.devices()) < 2:
        pytest.skip("needs 2 CPU devices (conftest sets XLA_FLAGS)")
    net = _tiny()
    draft = _tiny()
    rs = np.random.RandomState(2)
    sys_prompt = rs.randint(1, 250, size=18)
    prompts = [np.concatenate([sys_prompt, rs.randint(1, 250, size=n)])
               for n in (4, 5, 6, 7)]
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(2, 1), ("dp", "tp"))
    cfg = ServerConfig(max_batch=2, max_length=64, min_length=8,
                       num_slots=2, block_size=8, summary_every=1 << 30,
                       draft_net=draft, spec_k=3, radix_cache=True)
    retrace.enable(mode="warn")
    try:
        srv = serving.GenerativeServer(net, cfg, mesh=mesh)
        with srv:
            warm = [srv.submit(p, max_new_tokens=8) for p in prompts]
            for f in warm:
                f.result(180)
            retrace.warm()
            futs = [srv.submit(p, max_new_tokens=8) for p in prompts]
            outs = [f.result(180) for f in futs]
            stats = srv.stats()
            for rep in srv.replicas:
                rep.mgr.check()
        violations = retrace.violations()
    finally:
        retrace.disable()
        retrace.reset()
    for p, r in zip(prompts, outs):
        o = net.generate(nd.array(p[None]), 8).asnumpy()[0]
        assert np.array_equal(r, o)
    assert violations == []
    assert stats["num_replicas"] == 2
    assert stats["radix_cache"]["hits"] > 0
    assert stats["speculative"]["draft_tokens"] > 0
    verified = 0
    for rep in srv.replicas:
        sigs = rep.engine.compiled_signatures()
        assert ("step",) not in sigs        # spec mode never compiles it
        verified += sigs.count(("verify",))
        assert sigs.count(("verify",)) <= 1
        draft_sigs = rep.draft.compiled_signatures()
        assert ("verify",) not in draft_sigs
        assert draft_sigs.count(("step",)) <= 1
        # at drain the only live blocks are the prefix cache's own
        assert rep.mgr.allocator.blocks_in_use == \
            len(rep.radix.block_refs())
    assert verified >= 1                    # at least one replica decoded

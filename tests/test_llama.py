"""Llama family + flash attention tests (new capability vs the reference —
SURVEY §5 long-context ABSENT; test strategy mirrors the reference's op
unit tests + consistency cross-checks, SURVEY §4)."""
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd, parallel
from mxnet_tpu.models import llama


def _ids(b, t, vocab=256, seed=0):
    return nd.array(onp.random.RandomState(seed).randint(0, vocab, (b, t)),
                    dtype="int32")


def test_flash_attention_matches_reference():
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops.flash_attention import (_sdpa_ref,
                                               flash_attention_raw)

    rng = onp.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.normal(size=(2, 4, 64, 32)).astype("f"))
               for _ in range(3))
    for causal in (False, True):
        out = flash_attention_raw(q, k, v, causal, None)
        ref = _sdpa_ref(q, k, v, causal, 1 / onp.sqrt(32))
        assert float(jnp.abs(out - ref).max()) < 1e-4
        grads = jax.grad(
            lambda a, b, c: (flash_attention_raw(a, b, c, causal,
                                                 None) ** 2).sum(),
            argnums=(0, 1, 2))(q, k, v)
        refg = jax.grad(
            lambda a, b, c: (_sdpa_ref(a, b, c, causal,
                                       1 / onp.sqrt(32)) ** 2).sum(),
            argnums=(0, 1, 2))(q, k, v)
        for g, r in zip(grads, refg):
            assert float(jnp.abs(g - r).max()) < 1e-4


def test_flash_attention_chunked_backward():
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import flash_attention as fa

    rng = onp.random.RandomState(1)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 2, 256, 16)).astype("f"))
               for _ in range(3))
    g = jnp.asarray(rng.normal(size=(1, 2, 256, 16)).astype("f"))
    o = fa._sdpa_ref(q, k, v, True, 0.25)
    # small block forces the multi-block scan path
    dq, dk, dv = fa._fa_backward(q, k, v, o, g, True, 0.25, block=64)
    dq2, dk2, dv2 = fa._fa_backward_dense(
        q, k, v, g, q, k, v, True, 0.25, 256, 256)
    for a, b in ((dq, dq2), (dk, dk2), (dv, dv2)):
        assert float(jnp.abs(a - b).max()) < 1e-4


def test_flash_attention_degenerate_fully_masked_rows():
    """Causal with tq > tk leaves the leading (tq - tk) query rows with
    ZERO visible keys.  The flash convention (and the pallas kernel's
    online softmax) outputs ZEROS for such rows; the dense softmax
    reference produces NaN (0/0).  Pin the zero-output semantics so the
    TPU kernel and the chunked CPU fallback stay aligned and the
    behavior change vs a NaN-propagating dense path is a documented
    contract, not an accident (ADVICE r4)."""
    import jax.numpy as jnp

    from mxnet_tpu.ops.flash_attention import (_fa_forward_chunked,
                                               flash_attention_raw)

    rng = onp.random.RandomState(2)
    tq, tk = 8, 5
    q = jnp.asarray(rng.normal(size=(1, 2, tq, 16)).astype("f"))
    k = jnp.asarray(rng.normal(size=(1, 2, tk, 16)).astype("f"))
    v = jnp.asarray(rng.normal(size=(1, 2, tk, 16)).astype("f"))
    n_masked = tq - tk
    for out in (flash_attention_raw(q, k, v, True, None),
                _fa_forward_chunked(q, k, v, True, 0.25, block=4)):
        out = onp.asarray(out)
        assert onp.isfinite(out).all(), "NaN leaked from masked rows"
        assert (out[:, :, :n_masked] == 0).all(), \
            "fully-masked query rows must be exactly zero"
        assert (onp.abs(out[:, :, n_masked:]) > 0).any()


def test_rmsnorm():
    ln = llama.RMSNorm(8)
    ln.initialize()
    x = nd.random.uniform(-2, 2, shape=(2, 3, 8))
    out = ln(x).asnumpy()
    xa = x.asnumpy()
    want = xa / onp.sqrt((xa ** 2).mean(-1, keepdims=True) + 1e-5)
    onp.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-6)


def test_rope_rotation_properties():
    from mxnet_tpu.models.llama import _apply_rope, _rope_tables
    import jax.numpy as jnp

    cos, sin = _rope_tables(16, 8, 10000.0)
    x = jnp.asarray(onp.random.RandomState(0).normal(
        size=(1, 2, 16, 8)).astype("f"))
    out = _apply_rope(x, cos[None, None], sin[None, None])
    # norms preserved (rotation)
    onp.testing.assert_allclose(
        onp.asarray((out ** 2).sum(-1)), onp.asarray((x ** 2).sum(-1)),
        rtol=1e-4)
    # position 0 is identity
    onp.testing.assert_allclose(onp.asarray(out[:, :, 0]),
                                onp.asarray(x[:, :, 0]), rtol=1e-6)


def test_llama_tiny_forward_and_train():
    net = llama.llama_tiny()
    net.initialize(mx.init.Xavier())
    ids = _ids(2, 32)
    logits = net(ids)
    assert logits.shape == (2, 32, 256)
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 1e-3})
    labels = _ids(2, 32, seed=1)
    first = None
    for _ in range(5):
        with autograd.record():
            lg = net(ids)
            loss = nd.softmax_cross_entropy(
                lg.reshape((-1, 256)), labels.reshape((-1,))).mean()
        loss.backward()
        trainer.step(1)
        first = first if first is not None else float(loss.asscalar())
    assert float(loss.asscalar()) < first


def test_llama_hybridize_consistent():
    net = llama.llama_tiny()
    net.initialize(mx.init.Xavier())
    ids = _ids(1, 16)
    eager = net(ids).asnumpy()
    net.hybridize()
    hybrid = net(ids).asnumpy()
    onp.testing.assert_allclose(eager, hybrid, rtol=1e-4, atol=1e-5)


def test_llama_gqa_heads():
    cfg = llama.LlamaConfig(**{**llama.LLAMA_CONFIGS["llama_tiny"],
                               "num_kv_heads": 1})
    net = llama.LlamaForCausalLM(cfg)
    net.initialize(mx.init.Xavier())
    out = net(_ids(1, 8))
    assert out.shape == (1, 8, 256)
    attn = net.model.layers[0].self_attn
    assert attn.k_proj.weight.shape[0] == cfg.head_dim  # 1 kv head


def test_llama_generate():
    net = llama.llama_tiny()
    net.initialize(mx.init.Xavier())
    out = net.generate(_ids(2, 4), max_new_tokens=3)
    assert out.shape == (2, 7)
    assert out.asnumpy()[:, :4].tolist() == _ids(2, 4).asnumpy().tolist()


@pytest.mark.parametrize("mode", ["ring", "ulysses"])
def test_llama_sequence_parallel_modes(mode):
    mesh = parallel.make_mesh({"dp": 2, "sp": 4})
    with parallel.mesh_scope(mesh):
        net = llama.llama_tiny(attn_mode=mode)
        net.initialize(mx.init.Xavier())
        llama.shard_llama(net, mesh)
        ids = parallel.shard_batch(_ids(2, 32), mesh)
        with autograd.record():
            lg = net(ids)
            loss = nd.softmax_cross_entropy(
                lg.reshape((-1, 256)),
                nd.zeros((2 * 32,), dtype="int32")).mean()
        loss.backward()
        assert onp.isfinite(float(loss.asscalar()))


def test_llama_tp_matches_single_device():
    ids = _ids(2, 16)
    net = llama.llama_tiny(attn_mode="sdpa")
    net.initialize(mx.init.Xavier())
    ref = net(ids).asnumpy()
    mesh = parallel.make_mesh({"dp": 2, "tp": 4})
    with parallel.mesh_scope(mesh):
        llama.shard_llama(net, mesh)
        got = net(parallel.shard_batch(ids, mesh)).asnumpy()
    onp.testing.assert_allclose(got, ref, rtol=2e-3, atol=2e-4)


def test_llama3_8b_config():
    cfg = llama.LlamaConfig(**llama.LLAMA_CONFIGS["llama3_8b"])
    assert cfg.head_dim == 128
    assert cfg.num_kv_heads == 8
    assert cfg.vocab_size == 128256


def test_kv_cache_decoder_logits_parity():
    """Jitted KV-cache decode must produce the same logits as the full
    forward at every position (the anti-drift pin for LlamaDecoder)."""
    mx.random.seed(0)
    net = llama.llama_tiny(attn_mode="sdpa")
    net.initialize(mx.init.Xavier())
    ids = _ids(2, 12)
    ref = net(ids).asnumpy()                       # (B, T, V)
    dec = llama.LlamaDecoder(net, max_len=12)
    got = dec.logits_at(ids.asnumpy())
    onp.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)


def test_kv_cache_generate_matches_oracle():
    mx.random.seed(1)
    net = llama.llama_tiny(attn_mode="sdpa")
    net.initialize(mx.init.Xavier())
    prompt = _ids(2, 5, seed=3)
    slow = net.generate(prompt, max_new_tokens=6, use_cache=False)
    fast = net.generate(prompt, max_new_tokens=6, use_cache=True)
    assert fast.shape == slow.shape == (2, 11)
    assert fast.asnumpy().tolist() == slow.asnumpy().tolist()


def test_kv_cache_rejects_overflow_and_moe():
    net = llama.llama_tiny(attn_mode="sdpa")
    net.initialize(mx.init.Xavier())
    dec = llama.LlamaDecoder(net, max_len=6)
    with pytest.raises(mx.MXNetError):
        dec.generate(_ids(1, 4).asnumpy(), max_new_tokens=5)
    moe_net = llama.mixtral_tiny(attn_mode="sdpa")
    moe_net.initialize(mx.init.Xavier())
    with pytest.raises(mx.MXNetError):
        llama.LlamaDecoder(moe_net, max_len=8)
    # MoE generate falls back to the oracle path
    out = moe_net.generate(_ids(1, 3), max_new_tokens=2)
    assert out.shape == (1, 5)


def test_kv_cache_zero_tokens_and_bucket_reuse():
    mx.random.seed(2)
    net = llama.llama_tiny(attn_mode="sdpa")
    net.initialize(mx.init.Xavier())
    p = _ids(1, 4)
    out = net.generate(p, max_new_tokens=0)
    assert out.asnumpy().tolist() == p.asnumpy().tolist()

    # nearby prompt lengths / token counts share one compiled program.
    # Assert the DELTA, not the absolute count: jax's global jit cache
    # evicts entries under the full suite's compile churn, so absolute
    # sizes are environment-dependent (second call may even recompile
    # after eviction — what must never happen is a NEW signature).
    dec = llama.LlamaDecoder(net, max_len=64)
    # the eviction-proof invariant: both calls resolve to the SAME
    # (prompt, steps) buckets, so they share one compiled signature
    assert dec._bucket(5) == dec._bucket(7)
    assert dec._bucket(3) == dec._bucket(4)
    r5 = dec.generate(_ids(1, 5, seed=5).asnumpy(), 3)
    after_first = dec._gen._cache_size()
    r7 = dec.generate(_ids(1, 7, seed=7).asnumpy(), 4)
    assert r5.shape == (1, 8) and r7.shape == (1, 11)
    assert dec._gen._cache_size() <= after_first, \
        "bucketing failed: second generate added a new compiled signature"
    # padded-prompt result must equal exact-shape decode
    import jax as _jax
    import jax.numpy as _jnp
    import numpy as _np

    dec_exact = llama.LlamaDecoder(net, max_len=64)
    exact = dec_exact._gen(dec_exact._weights(),
                           _jnp.asarray(_ids(1, 5, seed=5).asnumpy(),
                                        _jnp.int32),
                           _jnp.int32(5), _jax.random.PRNGKey(0),
                           _jnp.float32(1.0), _jnp.float32(1.0),
                           3, 0, False, False)
    _np.testing.assert_array_equal(r5[:, 5:], _np.asarray(exact)[:, :3])


def test_sampling_modes():
    mx.random.seed(3)
    net = llama.llama_tiny(attn_mode="sdpa")
    net.initialize(mx.init.Xavier())
    p = _ids(2, 6, seed=9)
    greedy = net.generate(p, max_new_tokens=8)

    # temperature -> 0 converges to greedy
    cold = net.generate(p, max_new_tokens=8, do_sample=True,
                        temperature=1e-4, seed=0)
    assert cold.asnumpy().tolist() == greedy.asnumpy().tolist()
    # top_k=1 is argmax regardless of temperature
    k1 = net.generate(p, max_new_tokens=8, do_sample=True,
                      temperature=5.0, top_k=1, seed=1)
    assert k1.asnumpy().tolist() == greedy.asnumpy().tolist()
    # same seed reproduces; sampling is well-formed with top_p
    s_a = net.generate(p, max_new_tokens=8, do_sample=True,
                       temperature=1.0, top_p=0.9, seed=42)
    s_b = net.generate(p, max_new_tokens=8, do_sample=True,
                       temperature=1.0, top_p=0.9, seed=42)
    assert s_a.asnumpy().tolist() == s_b.asnumpy().tolist()
    assert s_a.shape == (2, 14)
    # sampled ids stay in-vocab
    assert int(s_a.asnumpy().max()) < 256 and int(s_a.asnumpy().min()) >= 0


def test_greedy_generate_leaves_rng_untouched():
    from mxnet_tpu import random as mx_random

    mx.random.seed(11)
    net = llama.llama_tiny(attn_mode="sdpa")
    net.initialize(mx.init.Xavier())
    mx.random.seed(11)
    before = mx_random.uniform(shape=(4,)).asnumpy()
    mx.random.seed(11)
    net.generate(_ids(1, 4), max_new_tokens=2)  # greedy: no RNG draw
    after = mx_random.uniform(shape=(4,)).asnumpy()
    onp.testing.assert_array_equal(before, after)


def test_generate_rejects_beyond_context():
    """prompt + max_new_tokens past cfg.max_seq_len must error, not
    silently build RoPE/KV state outside the trained window."""
    net = llama.llama_tiny()  # max_seq_len=128
    net.initialize(mx.init.Xavier())
    with pytest.raises(mx.MXNetError, match="max_seq_len"):
        net.generate(_ids(1, 4), max_new_tokens=200)


def test_scan_layers_matches_loop():
    """cfg.scan_layers (lax.scan over the stacked decoder, r4): loss
    and EVERY parameter gradient must equal the python layer loop —
    eager AND hybridized."""
    import numpy as np

    rs = np.random.RandomState(0)
    ids = nd.array(rs.randint(0, 256, (2, 16)), dtype="int32")
    labels = nd.array(rs.randint(0, 256, (2, 16)), dtype="int32")

    results = {}
    for scan in (False, True):
        mx.random.seed(5)
        net = llama.llama_tiny(num_layers=4, attn_mode="sdpa",
                               scan_layers=scan)
        net.initialize()
        with autograd.record():
            logits = net(ids)
            loss = nd.softmax_cross_entropy(
                logits.reshape((-1, 256)),
                labels.reshape((-1,))).mean()
        loss.backward()
        grads = {k: p.grad().asnumpy()
                 for k, p in net._collect_params_with_prefix().items()
                 if p.grad_req != "null"}
        results[scan] = (float(loss.asscalar()), grads, net)

    l0, g0, _ = results[False]
    l1, g1, net_scan = results[True]
    np.testing.assert_allclose(l1, l0, rtol=1e-5, atol=1e-6)
    assert g0.keys() == g1.keys()
    for k in g0:
        np.testing.assert_allclose(g1[k], g0[k], rtol=1e-4, atol=1e-5,
                                   err_msg=k)

    # hybridized scan path: same logits, and a Trainer step stays finite
    net_scan.hybridize(static_alloc=True)
    logits_h = net_scan(ids).asnumpy()
    mx.random.seed(5)
    net_ref = llama.llama_tiny(num_layers=4, attn_mode="sdpa")
    net_ref.initialize()
    np.testing.assert_allclose(logits_h, net_ref(ids).asnumpy(),
                               rtol=1e-4, atol=1e-5)
    trainer = gluon.Trainer(net_scan.collect_params(), "sgd",
                            {"learning_rate": 0.01})
    with autograd.record():
        loss = nd.softmax_cross_entropy(
            net_scan(ids).reshape((-1, 256)),
            labels.reshape((-1,))).mean()
    loss.backward()
    trainer.step(2)
    for k, p in net_scan._collect_params_with_prefix().items():
        assert np.isfinite(p.data().asnumpy()).all(), k


def test_scan_layers_on_tp_mesh_matches_loop():
    """scan_layers must compose with GSPMD sharding: the scanned stack
    over megatron-TP-sharded params on a dp x tp mesh produces the same
    loss and gradients as the python layer loop on the same mesh."""
    import numpy as np

    rs = np.random.RandomState(0)
    ids_np = rs.randint(0, 256, (4, 16))
    labels_np = rs.randint(0, 256, (4, 16))

    results = {}
    mesh = parallel.make_mesh({"dp": 2, "tp": 2})
    for scan in (False, True):
        with parallel.mesh_scope(mesh):
            mx.random.seed(9)
            net = llama.llama_tiny(num_layers=4, attn_mode="sdpa",
                                   scan_layers=scan)
            net.initialize()
            llama.shard_llama(net, mesh)
            ids = parallel.shard_batch(nd.array(ids_np, dtype="int32"))
            labels = parallel.shard_batch(
                nd.array(labels_np, dtype="int32"))
            with autograd.record():
                logits = net(ids)
                loss = nd.softmax_cross_entropy(
                    logits.reshape((-1, 256)),
                    labels.reshape((-1,))).mean()
            loss.backward()
            grads = {k: p.grad().asnumpy()
                     for k, p in
                     net._collect_params_with_prefix().items()
                     if p.grad_req != "null"}
            results[scan] = (float(loss.asscalar()), grads)

    l0, g0 = results[False]
    l1, g1 = results[True]
    np.testing.assert_allclose(l1, l0, rtol=1e-5, atol=1e-6)
    assert g0.keys() == g1.keys()
    for k in g0:
        np.testing.assert_allclose(g1[k], g0[k], rtol=1e-4, atol=1e-5,
                                   err_msg=k)


def test_scan_layers_ring_attention_on_mesh():
    """scan_layers x ring attention (dp x tp x sp): the scanned stack's
    jitted program must host the shard_map-based ring layers (eager
    scan evaluation of a shard_map body is NotImplemented in jax — the
    machinery jits the scan exactly for this) and match the loop."""
    import numpy as np

    rs = np.random.RandomState(0)
    ids_np = rs.randint(0, 256, (4, 32))
    mesh = parallel.make_mesh({"dp": 2, "tp": 2, "sp": 2})
    res = {}
    for scan in (False, True):
        with parallel.mesh_scope(mesh):
            mx.random.seed(9)
            net = llama.llama_tiny(num_layers=2, attn_mode="ring",
                                   scan_layers=scan)
            net.initialize()
            llama.shard_llama(net, mesh)
            ids = parallel.shard_batch(nd.array(ids_np, dtype="int32"))
            with autograd.record():
                loss = (net(ids).astype("float32") ** 2).mean()
            loss.backward()
            g = net.model.layers[1].mlp.down_proj.weight.grad().asnumpy()
            res[scan] = (float(loss.asscalar()), g)
    np.testing.assert_allclose(res[True][0], res[False][0], rtol=1e-5)
    np.testing.assert_allclose(res[True][1], res[False][1], rtol=1e-4,
                               atol=1e-5)


@pytest.mark.parametrize("mode,mesh_shape,factory", [
    ("ulysses", {"dp": 2, "sp": 4},
     lambda scan: llama.llama_tiny(num_layers=2, attn_mode="ulysses",
                                   scan_layers=scan)),
    ("moe", {"dp": 2, "ep": 2, "tp": 2},
     lambda scan: llama.mixtral_tiny(attn_mode="sdpa",
                                     moe_router="expert_choice",
                                     scan_layers=scan)),
], ids=["ulysses", "moe"])
def test_scan_layers_composes(mode, mesh_shape, factory):
    """scan_layers x {Ulysses sequence parallelism, MoE expert bank}:
    the scanned stack (the (L, E, ...) stacked expert weights included)
    must match the python loop on the sharded mesh."""
    import numpy as np

    rs = np.random.RandomState(0)
    ids_np = rs.randint(0, 256, (4, 16))
    mesh = parallel.make_mesh(mesh_shape)
    res = {}
    for scan in (False, True):
        with parallel.mesh_scope(mesh):
            mx.random.seed(9)
            net = factory(scan)
            net.initialize()
            llama.shard_llama(net, mesh)
            ids = parallel.shard_batch(nd.array(ids_np, dtype="int32"))
            with autograd.record():
                loss = (net(ids).astype("float32") ** 2).mean()
            loss.backward()
            # representative LAYER-STACKED grads: layer-1's mlp (the
            # (L, E, ...) expert bank for moe) + attention o_proj
            mlp = net.model.layers[1].mlp
            gw = (mlp.down_weight if hasattr(mlp, "down_weight")
                  else mlp.down_proj.weight).grad().asnumpy()
            go = net.model.layers[1].self_attn.o_proj.weight \
                .grad().asnumpy()
            res[scan] = (float(loss.asscalar()), gw, go)
    np.testing.assert_allclose(res[True][0], res[False][0], rtol=1e-5)
    np.testing.assert_allclose(res[True][1], res[False][1], rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_allclose(res[True][2], res[False][2], rtol=1e-4,
                               atol=1e-5)


def test_scan_layers_checkpoint_interop(tmp_path):
    """Parameters are per-layer regardless of scan_layers, so a
    checkpoint written by a loop-mode net must load into a scan-mode
    net (and vice versa) with identical outputs — users can flip the
    idiom without converting checkpoints."""
    import numpy as np

    rs = np.random.RandomState(0)
    ids = nd.array(rs.randint(0, 256, (2, 16)), dtype="int32")

    mx.random.seed(3)
    loop_net = llama.llama_tiny(num_layers=4, attn_mode="sdpa")
    loop_net.initialize()
    ref = loop_net(ids).asnumpy()
    pfile = str(tmp_path / "w.params")
    loop_net.save_parameters(pfile)

    mx.random.seed(99)  # different init — must be fully overwritten
    scan_net = llama.llama_tiny(num_layers=4, attn_mode="sdpa",
                                scan_layers=True)
    scan_net.initialize()
    scan_net.load_parameters(pfile)
    np.testing.assert_allclose(scan_net(ids).asnumpy(), ref,
                               rtol=1e-5, atol=1e-6)


def test_flash_pallas_shard_map_routing(monkeypatch):
    """GSPMD cannot auto-partition mosaic custom-calls: under a dp x tp
    mesh the pallas flash path must route through shard_map (batch over
    dp, heads over tp) and match the unsharded oracle.  On the CPU mesh
    the kernel body is stubbed with the chunked implementation — what's
    under test is the shard_map wiring (specs, divisibility fallback),
    which is exactly what real chips need (round-5 offline-topology
    find: the un-wrapped kernel fails to compile for any dp/tp mesh)."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu import parallel
    from mxnet_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    calls = {"sharded": 0}
    real_chunked = fa._fa_forward_chunked
    B, H, T, D = 4, 4, 128, 16

    def fake_pallas(q, k, v, causal, scale, **kw):
        calls["sharded"] += 1
        # PROOF the call executed under shard_map: the kernel must see
        # SHARD-LOCAL shapes (B/dp, H/tp), not the global ones — an
        # unwrapped call (the pre-fix bug) would pass every other
        # assert in this test
        assert q.shape == (B // 2, H // 2, T, D), q.shape
        return real_chunked(q, k, v, causal, scale)

    monkeypatch.setattr(fa, "_fa_forward_pallas", fake_pallas)

    rng = onp.random.RandomState(5)
    q, k, v = (jnp.asarray(rng.normal(size=(B, H, T, D)).astype("f"))
               for _ in range(3))
    oracle = fa._sdpa_ref(q, k, v, True, 0.25)

    mesh = parallel.make_mesh({"dp": 2, "tp": 2})
    with parallel.mesh_scope(mesh):
        out = jax.jit(lambda a, b, c: fa.flash_attention_raw(
            a, b, c, True, 0.25))(q, k, v)
    assert calls["sharded"] >= 1, "pallas path never engaged"
    assert float(jnp.abs(out - oracle).max()) < 1e-4

    # indivisible head count -> chunked fallback, still correct
    with parallel.mesh_scope(parallel.make_mesh({"dp": 2, "tp": 4})):
        q3 = q[:, :3]
        out3 = jax.jit(lambda a, b, c: fa.flash_attention_raw(
            a, b, c, True, 0.25))(q3, k[:, :3], v[:, :3])
    oracle3 = fa._sdpa_ref(q3, k[:, :3], v[:, :3], True, 0.25)
    assert float(jnp.abs(out3 - oracle3).max()) < 1e-4


def test_flash_inside_shard_map_body_no_nested_wrap(monkeypatch):
    """flash_attention_raw reached from INSIDE a shard_map body (the
    ring/ulysses sequence-parallel route) must call the kernel
    directly — wrapping a second shard_map over the same mesh is a
    trace-time ValueError (round-5 review repro)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from mxnet_tpu import parallel
    from mxnet_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    monkeypatch.setattr(
        fa, "_fa_forward_pallas",
        lambda q, k, v, c, s, **kw: fa._fa_forward_chunked(q, k, v, c, s))

    rng = onp.random.RandomState(6)
    B, H, T, D = 4, 2, 128, 16
    q, k, v = (jnp.asarray(rng.normal(size=(B, H, T, D)).astype("f"))
               for _ in range(3))
    oracle = fa._sdpa_ref(q, k, v, True, 0.25)

    mesh = parallel.make_mesh({"dp": 2, "sp": 2})
    spec = P("dp", None, None, None)
    with parallel.mesh_scope(mesh):
        out = jax.jit(jax.shard_map(
            lambda a, b, c: fa.flash_attention_raw(a, b, c, True, 0.25),
            mesh=mesh, in_specs=(spec, spec, spec),
            out_specs=spec))(q, k, v)
    assert float(jnp.abs(out - oracle).max()) < 1e-4


def _interp_kernels(monkeypatch):
    """Force the pallas path with interpret-mode kernels (CPU)."""
    import functools as _ft

    from mxnet_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    monkeypatch.setattr(
        fa, "_fa_forward_pallas",
        _ft.partial(fa._fa_forward_pallas, interpret=True))
    monkeypatch.setattr(
        fa, "_fa_backward_pallas",
        _ft.partial(fa._fa_backward_pallas, interpret=True))
    return fa


def _train_operands(shape, dtype, seed=3, n=3):
    import jax.numpy as jnp

    rng = onp.random.RandomState(seed)
    return tuple(jnp.asarray(rng.normal(size=shape).astype("f"), dtype)
                 for _ in range(n))


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("shape,dtype", [
    ((2, 2, 256, 32), "float32"),
    ((4, 12, 128, 64), "bfloat16"),     # BERT-base's heads, rows of 128
], ids=["f32_2x2x256x32", "bf16_4x12x128x64"])
def test_flash_pallas_backward_kernels_match_oracle(monkeypatch, causal,
                                                    shape, dtype):
    """The full custom-vjp path with PALLAS kernels both directions
    (interpret mode): forward saves lse, backward runs the two-kernel
    dq/dkv design, gradients match the dense vjp oracle.  Both shapes are
    one tile a head, so a grid step takes several (batch, head) rows
    (``train_tiles``); in bf16 the oracle is the float32 attention of the
    same rounded operands and the tolerance a few bf16 steps of the
    largest value."""
    import jax
    import jax.numpy as jnp

    fa = _interp_kernels(monkeypatch)
    assert fa.train_tiles(shape[0] * shape[1], shape[2], shape[2],
                          shape[3]) > 1
    q, k, v = _train_operands(shape, dtype)
    scale = 1 / float(onp.sqrt(shape[-1]))
    f32 = dtype == "float32"

    def loss(fn):
        return lambda a, b, c: (fn(a, b, c).astype(jnp.float32) ** 2).sum()

    def oracle(a, b, c):
        return fa._sdpa_ref(*(x.astype(jnp.float32) for x in (a, b, c)),
                            causal, scale)

    def close(got, want, tol):
        got, want = (onp.asarray(x, onp.float32) for x in (got, want))
        bound = tol if f32 else tol * float(onp.abs(want).max())
        assert float(onp.abs(got - want).max()) < bound

    out = fa.flash_attention_raw(q, k, v, causal, scale)
    assert out.dtype == q.dtype
    close(out, oracle(q, k, v), 1e-4 if f32 else 2 ** -7)
    g = jax.grad(loss(lambda a, b, c: fa.flash_attention_raw(
        a, b, c, causal, scale)), argnums=(0, 1, 2))(q, k, v)
    r = jax.grad(loss(oracle), argnums=(0, 1, 2))(q, k, v)
    for got, want in zip(g, r):
        assert got.dtype == q.dtype
        close(got, want, 2e-4 if f32 else 2 ** -6)


def test_flash_pallas_backward_sharded(monkeypatch):
    """The pallas backward under a dp x tp GSPMD mesh: fwd and bwd both
    route through shard_map with shard-local kernels, grads match the
    unsharded oracle."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu import parallel

    fa = _interp_kernels(monkeypatch)
    rng = onp.random.RandomState(4)
    B, H, T, D = 4, 4, 128, 16
    q, k, v = (jnp.asarray(rng.normal(size=(B, H, T, D)).astype("f"))
               for _ in range(3))
    scale = 0.25

    def loss(a, b, c):
        return (fa.flash_attention_raw(a, b, c, True, scale) ** 2).sum()

    r = jax.grad(lambda a, b, c: (fa._sdpa_ref(
        a, b, c, True, scale) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
    mesh = parallel.make_mesh({"dp": 2, "tp": 2})
    with parallel.mesh_scope(mesh):
        g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    for got, want in zip(g, r):
        assert float(jnp.abs(got - want).max()) < 2e-4


def test_flash_pallas_backward_kill_switch(monkeypatch):
    """MXT_PALLAS_FLASH_BWD=0 keeps the chunked backward (the on-chip
    A/B lever) — gradients still correct."""
    import jax
    import jax.numpy as jnp

    fa = _interp_kernels(monkeypatch)
    monkeypatch.setenv("MXT_PALLAS_FLASH_BWD", "0")
    rng = onp.random.RandomState(5)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 2, 128, 16)).astype("f"))
               for _ in range(3))
    g = jax.grad(lambda a, b, c: (fa.flash_attention_raw(
        a, b, c, True, 0.25) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
    r = jax.grad(lambda a, b, c: (fa._sdpa_ref(
        a, b, c, True, 0.25) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
    for got, want in zip(g, r):
        assert float(jnp.abs(got - want).max()) < 2e-4


def test_ulysses_gradient_through_pallas_kernels(monkeypatch):
    """Sequence-parallel ulysses with the flash custom-vjp INSIDE the
    shard_map body: the backward must route to the pallas kernels
    directly (manual-mesh guard) and match the unsharded oracle."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu import parallel
    from mxnet_tpu.parallel.ring import ulysses_attention_raw

    fa = _interp_kernels(monkeypatch)
    # spy on the kernels: gradient parity alone would stay green if a
    # gate change silently rerouted to the jax.nn fallback
    calls = {"fwd": 0, "bwd": 0}
    real_fwd, real_bwd = fa._fa_forward_pallas, fa._fa_backward_pallas

    def spy_fwd(*a, **kw):
        calls["fwd"] += 1
        return real_fwd(*a, **kw)

    def spy_bwd(*a, **kw):
        calls["bwd"] += 1
        return real_bwd(*a, **kw)

    monkeypatch.setattr(fa, "_fa_forward_pallas", spy_fwd)
    monkeypatch.setattr(fa, "_fa_backward_pallas", spy_bwd)

    rng = onp.random.RandomState(7)
    B, H, T, D = 2, 4, 256, 16
    q, k, v = (jnp.asarray(rng.normal(size=(B, H, T, D)).astype("f"))
               for _ in range(3))
    scale = 0.25

    r = jax.grad(lambda a, b, c: (fa._sdpa_ref(
        a, b, c, True, scale) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)

    mesh = parallel.make_mesh({"sp": 4})
    with parallel.mesh_scope(mesh):
        g = jax.jit(jax.grad(
            lambda a, b, c: (ulysses_attention_raw(
                a, b, c, causal=True, scale=scale,
                mesh=mesh) ** 2).sum(),
            argnums=(0, 1, 2)))(q, k, v)
    assert calls["fwd"] >= 1 and calls["bwd"] >= 1, calls
    for got, want in zip(g, r):
        assert float(jnp.abs(got - want).max()) < 2e-4

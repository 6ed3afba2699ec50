#!/usr/bin/env python3
"""chip_smoke.py — the standing proof that today's code starts on today's chip.

One process drives the system's two main paths once, through the entry
points a user calls, at the full width of a model the repo supports, and
checks what comes out by the repo's own means:

* **trainer** — ``models.bert.bert_base`` (vocab 30522), batch 64, seq 128,
  ``amp.init('bfloat16')``, ``hybridize(static_alloc=True)``,
  ``gluon.Trainer(..., 'adam')``, a hybridized loss, wrapped in
  ``gluon.FusedTrainStep``: a few fused executions on one fixed batch; the
  loss must be finite and lower at the end than at the start, and the lowered
  step must contain the Pallas flash-attention kernel (``tpu_custom_call``).
* **server** — ``GenerativeServer`` over ``LlamaForCausalLM`` at the
  ``llama3_8b`` widths (hidden 4096, 32/8 GQA heads, ffn 14336, vocab 128256),
  cut in depth only to ``SERVER_LAYERS``, seeded random bf16 weights, paged
  KV, ``max_length`` 1024: prompts of three lengths are submitted, every
  future must resolve, ``health()`` must be ``ok`` and no lane may have parked
  an error.  The cached decode path is checked against the uncached forward at
  logits level.
* **kernel** — ``flash_attention_raw`` forward+backward against ``_sdpa_ref``
  at highest matmul precision, at BERT-base's and Llama's geometry.

``--chips 4`` runs the same phases spread over four chips: the trainer under
``mx.tpu(mesh={'dp': 4})`` with ``kvstore='dist_tpu_sync'`` and
``parallel.shard_batch`` (per-step ``Trainer.step`` — the path that kvstore
supports), the server as four one-chip replicas behind ``ReplicaDispatcher``,
and asserts the work really is spread.

It fails (non-zero exit, no result line) when jax finds no TPU; there is no
CPU mode.  Weights and inputs are made from seeds; nothing is read from the
network or from an untracked file.  The last line of standard output is the
JSON object ``{"ok": true, "device": {"platform", "kind", "count"}}`` and
nothing more; the line before it (``summary: {...}``) and
``chiprun_out/chip_smoke_<n>chip.json`` carry the facts of the run.  Seconds
are reported as facts of this run (cold compile apart from run); no rate,
utilization or other speed figure is computed here.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chiprun_out")

#: Depth of the served Llama: half of the preset's 32 layers.  At the
#: llama3_8b widths one layer is 218 M parameters (0.44 GB in bf16) and
#: embedding + LM head are 2.1 GB, so 16 layers are 4.54 B parameters, 9.1 GB
#: of the chip's 16 GB.  That leaves room for the KV pool (0.27 GB), the
#: (4, 1024) prefill's float32 attention scores and MLP activations (about
#: 2 GB at their peak) and the decoder the logits check builds, with margin;
#: 24 layers (12.6 GB) would not leave it.
SERVER_LAYERS = 16

#: jax's own compile-pipeline events (jax/_src/dispatch.py).  The backend
#: event wraps the persistent-cache lookup, so on a warm cache it measures
#: the retrieval; tracing and lowering are python-side and never cached.
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
_TRACE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                 "/jax/core/compile/jaxpr_to_mlir_module_duration")


class CompileClock:
    """Accumulates jax's compile-pipeline events while the ``with`` block
    is open; ``lap()`` returns what was added since the last lap.
    Listeners fire on whichever thread compiles (the server's lanes
    included), hence the lock."""

    def __init__(self):
        import threading

        self._lock = threading.Lock()
        self._tot = self._zero()

    def __enter__(self):
        import jax.monitoring as mon

        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax.monitoring as mon

        mon.unregister_event_duration_listener(self._on_duration)
        mon.unregister_event_listener(self._on_event)
        return False

    @staticmethod
    def _zero():
        return {"compile_s": 0.0, "trace_lower_s": 0.0, "compiles": 0,
                "cache_hits": 0, "cache_misses": 0}

    def _on_duration(self, event, secs, **_kw):
        with self._lock:
            if event == _BACKEND_EVENT:
                self._tot["compile_s"] += secs
                self._tot["compiles"] += 1
            elif event in _TRACE_EVENTS:
                self._tot["trace_lower_s"] += secs

    def _on_event(self, event, **_kw):
        with self._lock:
            if event == "/jax/compilation_cache/cache_hits":
                self._tot["cache_hits"] += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self._tot["cache_misses"] += 1

    def lap(self):
        with self._lock:
            out, self._tot = self._tot, self._zero()
        out["compile_s"] = round(out["compile_s"], 2)
        out["trace_lower_s"] = round(out["trace_lower_s"], 2)
        return out


def require_tpu(chips):
    """The devices, or a non-zero exit naming what jax found instead."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, but jax found platform "
                 f"{devs[0].platform!r} ({len(devs)} device(s), kind "
                 f"{devs[0].device_kind!r}); there is no CPU mode")
    if len(devs) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} TPU devices, "
                 f"jax found {len(devs)}")
    return devs


def device_record(devs):
    """The device as jax reports it."""
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def result_line(devs):
    """The last line of standard output: these keys and no others."""
    return json.dumps({"ok": True, "device": device_record(devs)})


def _check(cond, msg):
    if not cond:
        raise AssertionError(msg)


def _bytes_in_use():
    """Per-device bytes in use as the backend reports them (None where
    it reports nothing, e.g. the CPU backend)."""
    import jax

    out = []
    for d in jax.devices():
        stats = d.memory_stats()
        out.append(None if not stats else int(stats["bytes_in_use"]))
    return out


# --- trainer ----------------------------------------------------------------

def train_phase(preset, clock, vocab=30522, batch=64, seq=128,
                steps_per_execution=8, executions=3, dp=None):
    """Pretraining steps on one fixed batch through stock gluon: what
    ``bench.py`` builds for its BERT leg, with nothing caught.

    ``preset`` is a ``models.bert`` builder.  One chip (``dp=None``):
    ``FusedTrainStep``, ``executions`` dispatches of
    ``steps_per_execution`` optimizer steps.  ``dp=n``: the batch sharded
    over an n-device 'dp' mesh, parameters replicated,
    ``kvstore='dist_tpu_sync'`` and as many per-step ``Trainer.step``
    calls."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import amp, autograd, gluon, nd, parallel

    t_phase = time.perf_counter()
    clock.lap()
    mx.random.seed(0)
    if dp:
        mx.tpu(mesh={"dp": dp})   # activates the mesh; params born on it
    try:
        net = preset(vocab_size=vocab)
        net.initialize(mx.init.Xavier())
        rng = np.random.RandomState(0)

        def place(a):
            a = nd.array(a, dtype="int32")
            return parallel.shard_batch(a) if dp else a

        ids = place(rng.randint(0, vocab, (batch, seq)))
        seg = place(np.zeros((batch, seq)))
        labels = place(rng.randint(0, vocab, (batch, seq)))
        net(ids, seg)  # resolve deferred shapes
        amp.init("bfloat16")
        net.hybridize(static_alloc=True)
        trainer = gluon.Trainer(
            net.collect_params(), "adam", {"learning_rate": 1e-4},
            **({"kvstore": "dist_tpu_sync"} if dp else {}))

        class _MLMLoss(gluon.HybridBlock):
            def hybrid_forward(self, F, mlm, lab):
                return F.softmax_cross_entropy(mlm, lab) / (batch * seq)

        loss_fn = _MLMLoss()
        loss_fn.hybridize()
        setup = clock.lap()

        mosaic_calls = None   # read from the one-chip fused step only
        if dp:
            def run():
                out = []
                for _ in range(steps_per_execution):
                    with autograd.record():
                        loss = loss_fn(net(ids, seg)[-1], labels)
                    loss.backward()
                    trainer.step(1)
                    out.append(float(loss.asnumpy().sum()))
                return out
        else:
            fstep = gluon.FusedTrainStep(
                net, trainer, lambda n, i, s, l: loss_fn(n(i, s)[-1], l),
                steps_per_execution=steps_per_execution, batch_size=1)

            def run():
                return [float(v) for v in fstep(ids, seg, labels).asnumpy()]

        t0 = time.perf_counter()
        losses = run()                       # traces, compiles, runs
        first_s = time.perf_counter() - t0
        first = clock.lap()
        t0 = time.perf_counter()
        for _ in range(executions - 1):
            losses += run()
        run_s = time.perf_counter() - t0
        steady = clock.lap()

        if not dp:
            from tools._tpu_topology import count_mosaic_calls

            mosaic_calls = count_mosaic_calls(
                fstep.lower(ids, seg, labels).as_text(dialect="hlo"))

        _check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
        head = float(np.mean(losses[:steps_per_execution]))
        tail = float(np.mean(losses[-steps_per_execution:]))
        _check(tail < head, f"loss did not fall: first execution mean "
                            f"{head:.4f}, last {tail:.4f}")
        _check(steady["compiles"] == 0 or dp,
               f"fused step recompiled in steady state: {steady}")
        spread = None
        if dp:
            spans = {len(p.data()._data.sharding.device_set)
                     for p in net.collect_params().values()}
            _check(spans == {dp}, f"parameters span {spans} devices, "
                                  f"expected {{{dp}}}")
            _check(len(ids._data.sharding.device_set) == dp and
                   not ids._data.sharding.is_fully_replicated,
                   "batch is not sharded over dp")
            spread = {"param_devices": dp, "bytes_in_use": _bytes_in_use()}
        return {
            "model": preset.__name__, "vocab": vocab, "batch": batch,
            "seq": seq, "dp": dp,
            "path": "Trainer.step+dist_tpu_sync" if dp
                    else "FusedTrainStep",
            "optimizer_steps": len(losses),
            "loss_first": round(losses[0], 4),
            "loss_last": round(losses[-1], 4),
            "mosaic_calls_in_step": mosaic_calls,
            "setup": setup,
            "first_execution_s": round(first_s, 2), "first": first,
            "run_s": round(run_s, 2),
            "steady_compiles": steady["compiles"],
            "spread": spread,
            "wall_s": round(time.perf_counter() - t_phase, 2),
        }
    finally:
        amp.turn_off()            # process-global; the server must not see it
        if dp:
            parallel.set_mesh(None)


# --- server -----------------------------------------------------------------

def serve_phase(preset, clock, prompt_lens=(24, 200, 600, 200, 24, 600),
                max_new_tokens=16, max_length=1024, num_slots=4, dp=None,
                **overrides):
    """``GenerativeServer`` over a seeded random ``LlamaForCausalLM``:
    submit the prompts twice (the first pass pays every compile, the
    second shows the server reusing them), then check the cached decoder
    against the uncached forward at logits level.

    ``preset`` is a ``models.llama`` builder and ``overrides`` its config
    overrides (depth).  ``dp=n`` serves from n one-device replicas."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import nd, parallel
    from mxnet_tpu.models import llama
    from mxnet_tpu.serving import GenerativeServer, ServerConfig

    t_phase = time.perf_counter()
    clock.lap()
    mx.random.seed(0)
    net = preset(max_seq_len=max_length, **overrides)
    cfg = net.config
    # serving weights: bf16 from birth and no gradient buffers, so the
    # device holds one copy of the model and nothing else
    net.cast("bfloat16")
    net.collect_params().setattr("grad_req", "null")
    net.initialize(mx.init.Normal(0.02))
    n_params = sum(int(np.prod(p.shape))
                   for p in net.collect_params().values())
    mesh = parallel.make_mesh({"dp": dp}) if dp else None
    server = GenerativeServer(
        net, ServerConfig(max_batch=4, max_length=max_length,
                          num_slots=num_slots,
                          max_new_tokens=max_new_tokens), mesh=mesh)
    setup = clock.lap()

    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in prompt_lens]

    def one_pass():
        t0 = time.perf_counter()
        futures = [server.submit(p) for p in prompts]
        # a bound on a hang, far above any cold compile, not a target
        outs = [f.result(timeout=900) for f in futures]
        return outs, time.perf_counter() - t0

    server.start()
    try:
        outs, cold_s = one_pass()
        cold = clock.lap()
        outs2, warm_s = one_pass()
        warm = clock.lap()
        health = server.health()
    finally:
        server.stop(drain=True)   # joins every lane; re-raises lane errors
    stats = server.stats()

    for p, o in zip(prompts + prompts, outs + outs2):
        _check(o.shape == (len(p) + max_new_tokens,), f"shape {o.shape}")
        _check((o[:len(p)] == p).all(), "prompt not echoed")
        _check(((o >= 0) & (o < cfg.vocab_size)).all(), "token out of range")
    _check(health["status"] == "ok", f"health: {health}")
    for rep in server.replicas:
        _check(rep.prefill.error is None and rep.decode.error is None,
               f"replica {rep.index} lane error: "
               f"{rep.prefill.error!r} / {rep.decode.error!r}")
    _check(stats["completed"] == 2 * len(prompts) and stats["failed"] == 0,
           f"stats: {stats}")
    sigs = [s for rep in server.replicas
            for s in rep.engine.compiled_signatures()]
    prefill_lengths = sorted({s[2] for s in sigs if s[0] == "prefill"})
    _check(len(prefill_lengths) >= 2, f"one prefill bucket only: {sigs}")
    _check(any(s[0] == "step" for s in sigs), "decode lane never stepped")
    # every request owes max_new_tokens - 1 decode ticks; fewer ticks in
    # total means some tick advanced several sequences at once
    owed = 2 * len(prompts) * (max_new_tokens - 1)
    _check(stats["decode_steps"] < owed,
           f"no continuous batching: {stats['decode_steps']} ticks for "
           f"{owed} tokens")

    spread = None
    if dp:
        homes = []
        for rep in server.replicas:
            eng = rep.engine
            devs = set(eng._w["emb"].sharding.device_set) | \
                set(eng._pool[0][0].sharding.device_set)
            _check(len(devs) == 1, f"replica {rep.index} spans {devs}")
            homes.append(next(iter(devs)).id)
        _check(len(set(homes)) == dp, f"replicas share devices: {homes}")
        per_rep = [r["completed"] for r in stats["replicas"]]
        _check(all(n > 0 for n in per_rep),
               f"a replica served nothing: {per_rep}")
        spread = {"replica_devices": homes, "replica_completed": per_rep,
                  "bytes_in_use": _bytes_in_use()}

    # Cached vs uncached logits, the pin tests/test_llama.py holds on the
    # CPU in float32.  Here every intermediate is rounded to bf16 (half
    # an ulp is 2^-9 relative) and the two paths round different tensors:
    # the uncached forward is one (1, T) pass, the decoder T one-token
    # steps against a cache.  The roundings add up like a random walk
    # over the ~10 rounded tensors of each layer, so the relative rms
    # difference of the logits should be near 2^-9 * sqrt(10 * layers):
    # 0.025 at 16 layers (measured on the v5e: 0.025 at 8 layers, 0.036
    # at 16).  The bound is 2^-8 * 4 * sqrt(layers), 0.0625 at 16 layers;
    # a wrong mask, position or cache row moves logits by their own size
    # (relative rms near 1).
    t0 = time.perf_counter()
    ids = prompts[0][None, :12]
    ref = net(nd.array(ids, dtype="int32")).asnumpy().astype(np.float32)
    dec = llama.LlamaDecoder(net, max_len=ids.shape[1])
    got = dec.logits_at(ids).astype(np.float32)
    rel_rms = float(np.sqrt(np.mean((got - ref) ** 2))
                    / np.sqrt(np.mean(ref ** 2)))
    tol = 2.0 ** -8 * 4 * float(np.sqrt(cfg.num_layers))
    _check(np.isfinite(got).all() and rel_rms <= tol,
           f"cached vs uncached logits: relative rms {rel_rms:.4g} > "
           f"{tol:.4g}")
    argmax_agree = float((got.argmax(-1) == ref.argmax(-1)).mean())
    check_s = time.perf_counter() - t0

    return {
        "model": preset.__name__, "layers": cfg.num_layers,
        "hidden": cfg.hidden_size, "ffn": cfg.intermediate_size,
        "heads": [cfg.num_heads, cfg.num_kv_heads],
        "vocab": cfg.vocab_size, "params": n_params,
        "dtype": "bfloat16", "max_length": max_length,
        "dp": dp, "requests": 2 * len(prompts),
        "prompt_lens": list(prompt_lens), "max_new_tokens": max_new_tokens,
        "prefill_length_buckets": prefill_lengths,
        "compiled_signatures": len(sigs),
        "decode_steps": stats["decode_steps"], "tokens_owed": owed,
        "health": health["status"],
        "setup": setup,
        "first_pass_s": round(cold_s, 2), "first_pass": cold,
        "run_s": round(warm_s, 2), "second_pass_compiles": warm["compiles"],
        "cached_vs_uncached": {"rel_rms": round(rel_rms, 5),
                               "tol": round(tol, 5),
                               "argmax_agree": round(argmax_agree, 3),
                               "check_s": round(check_s, 2)},
        "spread": spread,
        "wall_s": round(time.perf_counter() - t_phase, 2),
    }


# --- kernel -----------------------------------------------------------------

def kernel_phase(clock, shapes=(((64, 12, 128, 64), False),
                                ((1, 8, 2048, 128), True)), dp=None):
    """``flash_attention_raw`` forward and backward against ``_sdpa_ref``
    computed in float32 under ``jax.default_matmul_precision("highest")``
    from the same bf16 inputs.  ``shapes`` is ((B, H, T, D), causal)
    pairs; with ``dp`` the first is also run batch-sharded over an
    n-device mesh (the kernel inside ``shard_map``).

    Tolerance.  The kernel keeps scores and statistics in float32, but
    its in-kernel matmuls feed the MXU bf16 operands (p and ds are
    rounded to 2^-9 relative) and the results are stored as bf16 (another
    2^-9), and the backward chains two such contractions through the
    (dp - delta) cancellation; the r5 parity lane measured isolated
    elements 3% off.  So the check is on the tensor, not the element:
    relative rms error at most 2^-6, and no element further off than
    2^-4 of the tensor's largest value.  A wrong mask, scale or block
    index is off by the tensor's own size on most elements."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from mxnet_tpu import parallel
    from mxnet_tpu.ops.flash_attention import _sdpa_ref, flash_attention_raw
    from tools._tpu_topology import count_mosaic_calls

    t_phase = time.perf_counter()
    clock.lap()
    cases = [(shape, causal, None) for shape, causal in shapes]
    if dp:
        cases.append((shapes[0][0], shapes[0][1],
                      parallel.make_mesh({"dp": dp})))
    rows = []
    for shape, causal, mesh in cases:
        d = shape[-1]
        scale = 1.0 / float(np.sqrt(d))
        keys = jax.random.split(jax.random.PRNGKey(0), 4)
        q, k, v, g = (jax.random.normal(kk, shape, jnp.float32)
                      .astype(jnp.bfloat16) for kk in keys)
        if mesh is not None:
            sh = NamedSharding(mesh, P("dp"))
            q, k, v, g = (jax.device_put(a, sh) for a in (q, k, v, g))

        def kern(q, k, v, g, causal=causal, scale=scale):
            out, vjp = jax.vjp(
                lambda a, b, c: flash_attention_raw(a, b, c, causal, scale),
                q, k, v)
            return (out,) + vjp(g)

        def ref(q, k, v, g, causal=causal, scale=scale):
            f32 = [a.astype(jnp.float32) for a in (q, k, v)]
            out, vjp = jax.vjp(
                lambda a, b, c: _sdpa_ref(a, b, c, causal, scale), *f32)
            return (out,) + vjp(g.astype(jnp.float32))

        with parallel.mesh_scope(mesh):
            jk = jax.jit(kern)
            mosaic = count_mosaic_calls(
                jk.lower(q, k, v, g).as_text(dialect="hlo"))
            got = jax.block_until_ready(jk(q, k, v, g))
        with jax.default_matmul_precision("highest"):
            want = jax.block_until_ready(jax.jit(ref)(q, k, v, g))
        # forward, dq and dkv kernels
        _check(mosaic >= 3, f"{shape}: {mosaic} mosaic calls, the Pallas "
                            "path was not taken")
        errs = {}
        for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
            a = np.asarray(a.astype(jnp.float32))
            b = np.asarray(b)
            rel_rms = float(np.sqrt(np.mean((a - b) ** 2))
                            / np.sqrt(np.mean(b ** 2)))
            rel_max = float(np.abs(a - b).max() / np.abs(b).max())
            _check(np.isfinite(a).all() and rel_rms <= 2.0 ** -6
                   and rel_max <= 2.0 ** -4,
                   f"flash {name} at {shape} causal={causal}: relative "
                   f"rms {rel_rms:.4g} (<= {2.0 ** -6:.4g}), max "
                   f"{rel_max:.4g} (<= {2.0 ** -4:.4g})")
            errs[name] = [round(rel_rms, 5), round(rel_max, 5)]
        rows.append({"shape": list(shape), "causal": causal,
                     "sharded_over": None if mesh is None else dp,
                     "mosaic_calls": mosaic,
                     "rel_rms_and_max": errs})
    return {"cases": rows, "tol_rel_rms": 2.0 ** -6,
            "tol_rel_max": 2.0 ** -4, "compile": clock.lap(),
            "wall_s": round(time.perf_counter() - t_phase, 2)}


# --- driver -----------------------------------------------------------------

def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1 (default) or 4: spread both phases over four "
                         "chips and assert the spread")
    chips = ap.parse_args().chips
    t_start = time.perf_counter()

    import jax

    devs = require_tpu(chips)   # before anything of the repo is touched
    device = device_record(devs)

    import jaxlib
    from importlib import metadata

    import mxnet_tpu as mx
    from mxnet_tpu import _native
    from mxnet_tpu.models import bert, llama

    versions = {"python": sys.version.split()[0], "jax": jax.__version__,
                "jaxlib": jaxlib.__version__,
                "libtpu": metadata.version("libtpu")}
    native = {"available": _native.available(),
              "error": _native.build_error()}
    print(f"platform: {device['platform']}  device_kind: {device['kind']}  "
          f"devices: {device['count']}  chips used: {chips}", flush=True)
    print("versions: " + "  ".join(f"{k} {v}" for k, v in versions.items()),
          flush=True)
    print(f"compile cache: {jax.config.jax_compilation_cache_dir}",
          flush=True)
    print(f"native runtime: {native}", flush=True)

    dp = chips if chips > 1 else None
    summary = {"chips_used": chips, "versions": versions,
               "compile_cache_dir": jax.config.jax_compilation_cache_dir,
               "native_runtime": native, "phases": {}}

    def phase(name, fn, *args, **kw):
        print(f"[{name}] ...", flush=True)
        res = summary["phases"][name] = fn(*args, **kw)
        gc.collect()   # drop the phase's device arrays before the next
        res["bytes_in_use_after"] = _bytes_in_use()
        print(f"[{name}] passed: {json.dumps(res)}", flush=True)
        return res

    with CompileClock() as clock:
        kern = phase("kernel", kernel_phase, clock, dp=dp)
        train = phase("trainer", train_phase, bert.bert_base, clock, dp=dp)
        if not dp:
            _check(train["mosaic_calls_in_step"] >= 1,
                   "the fused trainer step holds no tpu_custom_call: "
                   "BERT's attention did not take the Pallas flash kernel")
        serve = phase("server", serve_phase, llama.llama3_8b, clock, dp=dp,
                      num_layers=SERVER_LAYERS)
    if dp:
        for name, res in (("trainer", train), ("server", serve)):
            used = res["spread"]["bytes_in_use"]
            _check(all(used) and max(used) <= 2 * min(used),
                   f"{name}: work is not spread evenly over the chips, "
                   f"bytes in use {used}")

    compile_s = {
        "kernel": kern["compile"]["compile_s"],
        "trainer": round(train["setup"]["compile_s"]
                         + train["first"]["compile_s"], 2),
        "server": round(serve["setup"]["compile_s"]
                        + serve["first_pass"]["compile_s"], 2),
    }
    summary["compile_s"] = compile_s
    summary["compile_s_total"] = round(sum(compile_s.values()), 2)
    summary["wall_s"] = round(time.perf_counter() - t_start, 2)
    os.makedirs(OUT_DIR, exist_ok=True)
    detail = os.path.join(OUT_DIR, f"chip_smoke_{chips}chip.json")
    with open(detail, "w") as f:
        json.dump({"device": device, **summary}, f, indent=1)
    print(f"detail: {detail}", flush=True)
    print("summary: " + json.dumps({
        "chips_used": chips, "versions": versions,
        "phases": {k: "passed" for k in summary["phases"]},
        "server_layers": serve["layers"],
        "mosaic_calls_in_trainer_step": train["mosaic_calls_in_step"],
        "compile_s": compile_s,
        "compile_s_total": summary["compile_s_total"],
        "run_s": {"trainer": train["run_s"], "server": serve["run_s"]},
        "native_runtime_available": native["available"],
        "wall_s": summary["wall_s"], "claim": None}), flush=True)
    # reached only when every phase passed: a failure has already left
    # through its exception, with no result line
    print(result_line(devs), flush=True)


if __name__ == "__main__":
    main()

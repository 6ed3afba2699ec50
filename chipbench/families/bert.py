"""Family ``bert``: builds ``models.bert.BERTModel`` + stock ``gluon.Trainer``
from a configuration file and a ``train_ring`` mix, drives the step (fused
K-step dispatches on one chip, per-step ``Trainer.step`` under a dp mesh), and
holds its first steps to the plain reference.

Only this file knows the program's names.  The parameters are the benchmark's:
made from the seed by ``references/bert.py``'s initialiser in one donated
jitted call and put into the net's parameters.  Set-up builds one step object,
drives it through the first dispatch (whose losses and optimizer state the
check reads), and the window drives that same object on.
"""
from __future__ import annotations

import gc
import time

import numpy as np


class Cell:
    kind = "train"
    programs = {"fused_step": r"^jit_k_steps"}

    def __init__(self, config, mix, seed, chips, span, reference):
        self.cfg, self.mix, self.seed, self.span = config, mix, seed, span
        self.ref, self.chips = reference, chips
        self.k = int(mix["steps_per_dispatch"])
        self.rows = int(mix["rows_per_chip"]) * chips
        self.seq = int(mix["seq"])
        self.net = None

    # -- set-up ---------------------------------------------------------------
    def build(self, phase, ring):
        import jax
        import mxnet_tpu as mx
        from mxnet_tpu import amp, gluon, nd
        from mxnet_tpu.models.bert import BERTModel

        cfg, mix = self.cfg, self.mix
        self.ring = ring
        self._mesh = mix.get("mesh")
        if self._mesh:
            mx.tpu(mesh=dict(self._mesh))   # activates the mesh; params born on it
        mx.random.seed(self.seed % (2 ** 31 - 1))   # the program's dropout masks
        with phase("weights"):
            net = BERTModel(
                vocab_size=cfg["vocab_size"],
                token_type_vocab_size=cfg["type_vocab_size"],
                num_layers=cfg["num_hidden_layers"], units=cfg["hidden_size"],
                hidden_size=cfg["intermediate_size"],
                num_heads=cfg["num_attention_heads"],
                max_length=cfg["max_position_embeddings"],
                dropout=cfg["hidden_dropout_prob"])

            class _Leave(mx.init.Initializer):
                """Parameters are born as device zeros and filled below."""

                def __call__(self, desc, arr):
                    pass

            net.initialize(_Leave())
            ids, seg, lab = (self._place(a[0]) for a in ring[0])
            net(ids, seg)   # resolves deferred shapes
            slots = self._slots(net)
            ref = self.ref
            key = jax.random.PRNGKey(self.seed % (2 ** 31 - 1))

            def fill(old, key):
                del old
                return ref.init_params(key, cfg)

            old = {n: p.data()._data for n, p in slots.items()}
            old_sharding = {n: a.sharding for n, a in old.items()}
            new = jax.jit(fill, donate_argnums=0)(old, key)
            del old
            for n, p in slots.items():
                # committed where the parameter lives, as parameters are from
                # birth: an uncommitted one makes the second step recompile
                p.set_data(nd.NDArray(jax.device_put(new[n], old_sharding[n])))
            del new
        with phase("trainer"):
            amp.init("bfloat16")
            net.hybridize(static_alloc=True)
            kw = {"kvstore": mix["kvstore"]} if mix.get("kvstore") else {}
            trainer = gluon.Trainer(net.collect_params(), "adam",
                                    {"learning_rate": mix["learning_rate"]}, **kw)
            n_tok = self.rows * self.seq

            class _MLMLoss(gluon.HybridBlock):
                def hybrid_forward(self, F, mlm, lab):
                    return F.softmax_cross_entropy(mlm, lab) / n_tok

            loss_fn = _MLMLoss()
            loss_fn.hybridize()
            self.net, self.trainer, self.loss_fn = net, trainer, loss_fn
            self._slots_by_name = slots
            if mix["fused"]:
                self.fstep = gluon.FusedTrainStep(
                    net, trainer, lambda n, i, s, l: loss_fn(n(i, s)[-1], l),
                    steps_per_execution=self.k, batch_size=1, stacked_inputs=True)
        with phase("first_steps"):
            # the check's side of the program: the first dispatch, through
            # the window's own call and feed
            n_chk = int(mix["check"]["steps"])
            if mix["fused"]:
                losses = self._fetch(self._dispatch(0))
                state = self._read_state()
            else:
                head = self._steps(0, range(n_chk))
                state = self._read_state()
                losses = self._fetch(head + self._steps(0, range(n_chk, self.k)))
            self.first = {"loss": losses[:n_chk], **state}
            self.first_dispatch_loss = float(np.mean(losses))
        with phase("warm_up"):
            self._fetch(self._dispatch(1))

    def _place(self, a):
        from mxnet_tpu import nd, parallel

        a = nd.array(a, dtype="int32")
        return parallel.shard_batch(a, axis=a.ndim - 2) if self._mesh else a

    @staticmethod
    def _slots(net):
        """Reference leaf name -> the program's Parameter."""
        out = {"word": net.word_embed.weight, "type": net.token_type_embed.weight,
               "pos": net.encoder.position_weight}

        def dense(prefix, layer):
            out[prefix + ".w"], out[prefix + ".b"] = layer.weight, layer.bias

        def norm(prefix, layer):
            out[prefix + ".g"], out[prefix + ".b"] = layer.gamma, layer.beta

        norm("emb_ln", net.encoder.layer_norm)
        for l, cell in enumerate(net.encoder.transformer_cells):
            att = cell.attention
            for n, layer in (("q", att.proj_query), ("k", att.proj_key),
                             ("v", att.proj_value), ("o", att.proj_out)):
                dense(f"l{l}.{n}", layer)
            norm(f"l{l}.ln_att", cell.layer_norm_att)
            dense(f"l{l}.ffn1", cell.ffn_1)
            dense(f"l{l}.ffn2", cell.ffn_2)
            norm(f"l{l}.ln_ffn", cell.layer_norm_ffn)
        dense("pooler", net.pooler)
        dense("nsp", net.classifier)
        dec = list(net.decoder)
        dense("mlm.dense", dec[0])
        norm("mlm.ln", dec[2])
        dense("mlm.out", dec[3])
        return out

    # -- the step, as set-up and the window both call it ----------------------
    def _lr(self, step):
        """The paper's linear warm-up: the rate of optimizer step ``step``
        (from 0)."""
        ramp = min(1.0, (step + 1) / float(self.mix["warmup_steps"]))
        return float(self.mix["learning_rate"]) * ramp

    def _steps(self, d, which):
        """Per-step path: steps ``which`` of dispatch ``d``; the losses stay
        on the device."""
        from mxnet_tpu import autograd

        ids, seg, lab = self.ring[d % len(self.ring)]
        out = []
        for k in which:
            self.trainer.set_learning_rate(self._lr(d * self.k + k))
            with self.span("bench.upload"):
                i, s, l = self._place(ids[k]), self._place(seg[k]), self._place(lab[k])
            with self.span("bench.dispatch"):
                with autograd.record():
                    loss = self.loss_fn(self.net(i, s)[-1], l)
                loss.backward()
                self.trainer.step(1)
            out.append(loss)
        return out

    def _dispatch(self, d):
        """One dispatch of K optimizer steps on ring entry ``d``."""
        if not self.mix["fused"]:
            return self._steps(d, range(self.k))
        # the fused step takes one rate a dispatch: that of its last step
        self.trainer.set_learning_rate(self._lr(d * self.k + self.k - 1))
        with self.span("bench.upload"):
            batch = [self._place(a) for a in self.ring[d % len(self.ring)]]
        with self.span("bench.dispatch"):
            return self.fstep(*batch)

    def _fetch(self, losses):
        with self.span("bench.fetch_loss"):
            if isinstance(losses, list):
                return [float(l.asnumpy().sum()) for l in losses]
            return [float(v) for v in losses.asnumpy()]

    def _read_state(self):
        """Per-leaf norms of Adam's first moment and of the parameters' change
        from the seed's values, worked out on the device in one call."""
        import jax
        import jax.numpy as jnp

        idx = {id(p): i for i, p in enumerate(self.trainer._params)}
        moments = {n: self.trainer._states[idx[id(p)]][0]._data
                   for n, p in self._slots_by_name.items()}
        seconds = {n: self.trainer._states[idx[id(p)]][1]._data
                   for n, p in self._slots_by_name.items()}
        weights = {n: p.data()._data for n, p in self._slots_by_name.items()}
        ref, cfg = self.ref, self.cfg

        def norms(m, v, w, key):
            w0 = ref.init_params(key, cfg)
            f32 = jnp.float32
            return ({n: jnp.sqrt(jnp.sum(jnp.square(a.astype(f32))))
                     for n, a in m.items()},
                    {n: jnp.sqrt(jnp.sum(a.astype(f32))) for n, a in v.items()},
                    {n: jnp.sqrt(jnp.sum(jnp.square(a.astype(f32) - w0[n])))
                     for n, a in w.items()})

        key = jax.random.PRNGKey(self.seed % (2 ** 31 - 1))
        moment, second, delta = jax.jit(norms)(moments, seconds, weights, key)
        return {"moment_norm": {n: float(v) for n, v in moment.items()},
                "second_moment_root": {n: float(v) for n, v in second.items()},
                "delta_norm": {n: float(v) for n, v in delta.items()}}

    # -- the window -----------------------------------------------------------
    def window(self, seconds, _ring):
        t0 = time.perf_counter()
        pending, n, d = None, 0, 2
        last, fetched = None, [t0]
        while time.perf_counter() - t0 < seconds:
            nxt = self._dispatch(d)
            if pending is not None:
                last = self._fetch(pending)
                fetched.append(time.perf_counter())
            pending, n, d = nxt, n + 1, d + 1
        last = self._fetch(pending)
        t1 = time.perf_counter()
        fetched.append(t1)
        between = [b - a for a, b in zip(fetched, fetched[1:])]
        self.last_dispatch_loss = float(np.mean(last))
        steps = n * self.k
        return {"kind": "train", "driver": "train_ring", "window_s": t1 - t0,
                "t0_abs": t0,
                "attempted": n, "failed": 0 if np.isfinite(last).all() else 1,
                "dispatches": n, "optimizer_steps": steps, "chips": self.chips,
                "longest_dispatch_s": max(between),
                "median_dispatch_s": float(np.median(between)),
                "tokens": steps * self.rows * self.seq,
                "rows": self.rows, "seq": self.seq,
                "loss_first_dispatch": self.first_dispatch_loss,
                "loss_last_dispatch": self.last_dispatch_loss}

    # -- after the window -----------------------------------------------------
    def finish(self):
        pass

    def end_window(self):
        """Free the program's state, so that the reference has the chip."""
        from mxnet_tpu import amp, parallel

        self.net = self.trainer = self.loss_fn = self.fstep = None
        self._slots_by_name = None
        amp.turn_off()
        if self._mesh:
            parallel.set_mesh(None)
        gc.collect()

    def check(self, control):
        chk = self.mix["check"]
        n = int(chk["steps"])
        ids, seg, lab = self.ring[0]
        batches = [(ids[k], seg[k], lab[k]) for k in range(n)]
        lrs = [self._lr(self.k - 1 if self.mix["fused"] else k) for k in range(n)]
        out = [("loss_rise_over_window",
                self.last_dispatch_loss - self.first_dispatch_loss, 0.0)]
        ref = self.ref.follow(self.cfg, self.seed, batches, lrs)
        for name, val in gaps(self.first, ref).items():
            limit = chk.get(name + "_limit") if name in READINGS else chk[name + "_limit"]
            out.append((name, val, limit))
        if control:
            # in the program's place, so with masks of its own
            low = self.ref.follow(self.cfg, self.seed, batches, lrs, lowp=True, masks=1)
            for name, val in gaps(low, ref).items():
                out.append(("control." + name, val, None))
        return out


#: shown beside the numbers that decide; the mix gives no limit for them
READINGS = ("loss_gap_first_step",)


def gaps(got, ref):
    """The numbers compared: the largest relative gap of a step's loss, the
    worst leaf's gap of norms, against the reference's norm of that leaf or of
    the median leaf, whichever is larger, and the gap of two norms over all
    leaves."""
    by_step = [abs(a - b) / abs(b) for a, b in zip(got["loss"], ref["loss"])]
    print("loss_gap_by_step:", " ".join(f"{g:.3e}" for g in by_step), flush=True)
    # the first step runs on the seed's own weights, so its gap is arithmetic
    # and masks alone; later steps add how far two trajectories have drifted
    out = {"loss_gap_first_step": float(by_step[0]), "loss_gap_max": float(max(by_step))}
    for what in ("moment_norm", "delta_norm"):
        r = ref[what]
        med = float(np.median(list(r.values())))
        out[what + "_gap_worst_leaf"] = float(max(
            abs(got[what][n] - r[n]) / max(r[n], med) for n in r))
    # over all leaves: the norm of the first moment, and the root of the sum of
    # the second (the steps' gradient norms, weighted).  A part of the batch
    # left out, or a gradient scaled wrongly, moves both by its share; the two
    # sides' dropout masks differ, and move the second by a third of the first
    whole = {k: float(np.sqrt(sum(v * v for v in d["moment_norm"].values())))
             for k, d in (("got", got), ("ref", ref))}
    out["moment_norm_gap_global"] = abs(whole["got"] - whole["ref"]) / whole["ref"]
    power = {k: float(np.sqrt(sum(v * v for v in d["second_moment_root"].values())))
             for k, d in (("got", got), ("ref", ref))}
    out["grad_power_gap_global"] = abs(power["got"] - power["ref"]) / power["ref"]
    return out

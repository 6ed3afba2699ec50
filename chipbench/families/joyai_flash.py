"""Family ``joyai_flash``: builds ``models.joyai_flash.JoyAIFlashForPretraining``
+ stock ``gluon.Trainer`` (AdamW) + ``FusedTrainStep`` from a configuration
file and a ``train_ring`` mix, drives K-step dispatches and holds the first of
them to the plain reference: both loss terms of every step, Adam's moments,
every leaf's change, and the choice bias the step moved without a gradient.

Only this file knows the program's names.  The parameters are the benchmark's:
made from the seed by ``references/joyai_flash.py``'s initialiser in one donated
jitted call and put into the net's parameters.  The sequences are made from the
ring's arrays so that BOTH heads have something to learn (uniform ids teach a
causal model nothing): a seeded first-order chain, the next id ``perm[current]``
where a bit of the ring's permuted fresh id is set (one half of the positions)
and the ring's fresh id otherwise.
"""
from __future__ import annotations

import gc
import time

import numpy as np


def chain(ring_ids, ring_labels, perm):
    """(.., T) fresh ids and their ring labels -> (.., T) int32 sequences."""
    take = (ring_labels & 1).astype(bool)
    out = np.array(ring_ids, dtype=np.int32)
    for t in range(1, out.shape[-1]):
        out[..., t] = np.where(take[..., t], perm[out[..., t - 1]], out[..., t])
    return out


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
    def _model_config(self):
        from mxnet_tpu.models.joyai_flash import JoyAIFlashConfig

        cfg, val = self.cfg, self.cfg["assumed_values"]
        return JoyAIFlashConfig(
            hidden_size=cfg["hidden_size"],
            intermediate_size=cfg["intermediate_size"],
            moe_intermediate_size=cfg["moe_intermediate_size"],
            num_layers=cfg["num_hidden_layers"],
            first_k_dense=cfg["first_k_dense_replace"],
            num_heads=cfg["num_attention_heads"],
            q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
            qk_nope_head_dim=cfg["qk_nope_head_dim"],
            qk_rope_head_dim=cfg["qk_rope_head_dim"],
            v_head_dim=cfg["v_head_dim"], num_experts=cfg["router_experts"],
            num_experts_per_tok=cfg["num_experts_per_tok"],
            n_shared_experts=cfg["n_shared_experts"],
            norm_topk_prob=cfg["norm_topk_prob"],
            routed_scaling_factor=cfg["routed_scaling_factor"],
            experts_held=tuple(cfg["experts_held"]),
            vocab_size=cfg["vocab_size"], rope_theta=cfg["rope_theta"],
            norm_eps=cfg["rms_norm_eps"],
            num_nextn_predict_layers=cfg["num_nextn_predict_layers"],
            mtp_loss_weight=val["mtp_loss_weight"],
            bias_update_speed=val["bias_update_speed"])

    def build(self, phase, ring):
        import jax
        import mxnet_tpu as mx
        from mxnet_tpu import amp, gluon, nd
        from mxnet_tpu.models import joyai_flash as program

        cfg, mix = self.cfg, self.mix
        opt = cfg["assumed_values"]["optimizer"]
        with phase("sequences"):
            perm = np.random.default_rng(
                [self.seed % (2 ** 63), 5]).permutation(cfg["vocab_size"])
            self.ring = [chain(ids, lab, perm.astype(np.int32))
                         for ids, _seg, lab in ring]
        with phase("weights"):
            net = program.JoyAIFlashForPretraining(self._model_config())
            net.set_remat(mix["remat"])

            class _Leave(mx.init.Initializer):
                """Parameters are born as device zeros and filled below."""

                def __call__(self, desc, arr):
                    pass

            net.initialize(_Leave())
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
            if cfg["torch_dtype"] == "bfloat16":
                amp.init("bfloat16")
            net.hybridize(static_alloc=True)
            trainer = gluon.Trainer(
                net.collect_params(), "adamw",
                {"learning_rate": mix["learning_rate"], "beta1": opt["beta1"],
                 "beta2": opt["beta2"], "epsilon": opt["eps"],
                 "wd": opt["weight_decay"]})
            self.net, self.trainer = net, trainer
            self._slots_by_name = slots
            self.fstep = gluon.FusedTrainStep(
                net, trainer, program.pretrain_forward_loss,
                steps_per_execution=self.k, batch_size=1, stacked_inputs=True)
            # the fused program takes its gradients inside: the eager
            # buffers are 4 bytes a parameter of dead weight beside it
            self.fstep.free_grad_buffers()
            self._count = program.count_reported
        with phase("first_steps"):
            # the check's side of the program: the first dispatch, through
            # the window's own call and feed
            losses, values = self._fetch(self._dispatch(0))
            n_chk = int(mix["check"]["steps"])
            self.first = {"loss": losses[:n_chk],
                          **{n: values[n][:n_chk] for n in
                             ("loss_main", "loss_mtp", "expert_rows")},
                          **self._read_state()}
            self.first_dispatch = self._means(losses, values)
        with phase("warm_up"):
            self._fetch(self._dispatch(1))

    @staticmethod
    def _slots(net):
        """Reference leaf name -> the program's Parameter."""
        out = {"embed": net.model.top.embed, "head": net.model.top.head,
               "norm": net.model.top.norm}
        for l, layer in enumerate(net.model.layers):
            for n in layer._names:
                if n != "expert_bias":
                    out[f"l{l}.{n}"] = getattr(layer, n)
        for n in ("e_norm", "h_norm", "eh_proj", "norm"):
            out["mtp." + n] = getattr(net.mtp, n)
        for n in net.mtp_layer._names:
            if n != "expert_bias":
                out["mtp.l." + n] = getattr(net.mtp_layer, n)
        return out

    def _biases(self):
        layers = [l for l in self.net.model.layers if not l._dense]
        return [l.expert_bias for l in layers + [self.net.mtp_layer]]

    # -- the step, as set-up and the window both call it ----------------------
    def _lr(self, step):
        """Linear warm-up: the rate of optimizer step ``step`` (from 0)."""
        ramp = min(1.0, (step + 1) / float(self.mix["warmup_steps"]))
        return float(self.mix["learning_rate"]) * ramp

    def _dispatch(self, d):
        """One dispatch of K optimizer steps on ring entry ``d``."""
        from mxnet_tpu import nd

        # the fused step takes one rate a dispatch: that of its last step
        self.trainer.set_learning_rate(self._lr(d * self.k + self.k - 1))
        with self.span("bench.upload"):
            ids = nd.array(self.ring[d % len(self.ring)], dtype="int32")
        with self.span("bench.dispatch"):
            return self.fstep(ids)

    def _fetch(self, losses):
        """The K losses of the oldest dispatch in flight and what its steps
        reported."""
        with self.span("bench.fetch_loss"):
            out = [float(v) for v in losses.asnumpy()]
            values = self.fstep.fetch_reported()
        self._count(values)
        return out, values

    @staticmethod
    def _means(losses, values):
        return {"loss": float(np.mean(losses)),
                "loss_main": float(np.mean(values["loss_main"])),
                "loss_mtp": float(np.mean(values["loss_mtp"]))}

    def _read_state(self):
        """Per-leaf norms of Adam's moments and of the parameters' change
        from the seed's values, worked out on the device in one call, and the
        choice bias."""
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
                "delta_norm": {n: float(v) for n, v in delta.items()},
                "bias": np.stack([b.data().asnumpy() for b in self._biases()]),
                "bias_has_optimizer_state": float(any(
                    self.trainer._states[idx[id(b)]] is not None
                    for b in self._biases() if id(b) in idx))}

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
        self.last_dispatch = self._means(*last)
        steps = n * self.k
        return {"kind": "train", "driver": "train_ring", "window_s": t1 - t0,
                "t0_abs": t0,
                "attempted": n, "failed": 0 if np.isfinite(last[0]).all() else 1,
                "dispatches": n, "optimizer_steps": steps, "chips": self.chips,
                "longest_dispatch_s": max(between),
                "median_dispatch_s": float(np.median(between)),
                "tokens": steps * self.rows * self.seq,
                "rows": self.rows, "seq": self.seq,
                "loss_first_dispatch": self.first_dispatch["loss"],
                "loss_last_dispatch": self.last_dispatch["loss"],
                "step_facts": dict(self.fstep.facts)}

    # -- after the window -----------------------------------------------------
    def finish(self):
        pass

    def end_window(self):
        """Free the program's state, so that the reference has the chip."""
        from mxnet_tpu import amp

        self.net = self.trainer = self.fstep = None
        self._slots_by_name = None
        amp.turn_off()
        gc.collect()

    def check(self, control):
        chk = self.mix["check"]
        n = int(chk["steps"])
        batches = [self.ring[0][k] for k in range(n)]
        lrs = [self._lr(self.k - 1)] * n
        rise = {k: self.last_dispatch[k] - self.first_dispatch[k]
                for k in self.first_dispatch}
        out = [("loss_rise_over_window", rise["loss"], 0.0),
               ("loss_main_rise_over_window", rise["loss_main"], None),
               ("loss_mtp_rise_over_window", rise["loss_mtp"], None),
               ("bias_has_optimizer_state",
                self.first["bias_has_optimizer_state"], 0.0)]
        speed = self.cfg["assumed_values"]["bias_update_speed"]
        ref = self.ref.follow(self.cfg, self.seed, batches, lrs)
        for name, val in gaps(self.first, ref, speed).items():
            out.append((name, val, chk.get(name + "_limit")))
        if control:
            # each in the program's place: held to the program's limits, and
            # held to fail one of them
            for tag, kw in (("control", {"lowp": True}),
                            ("control_no_mtp", {"mtp": False})):
                low = self.ref.follow(self.cfg, self.seed, batches, lrs, **kw)
                passes = 1.0
                for name, val in gaps(low, ref, speed).items():
                    out.append((f"{tag}.{name}", val, None))
                    limit = chk.get(name + "_limit")
                    if limit is not None and val > limit:
                        passes = 0.0
                out.append((f"{tag}.passes_every_limit", passes, 0.0))
        return out


def gaps(got, ref, speed):
    """The numbers compared: the largest relative gap of each loss term over
    the steps; the worst leaf's gap of norms (against the reference's norm of
    that leaf or of the median leaf, whichever is larger) and the gap of two
    norms over all leaves; the choice bias against the reference's; and, as a
    reading, the pairs routed elsewhere."""
    out = {}
    for term in ("loss_main", "loss_mtp"):
        by_step = [abs(a - b) / abs(b) for a, b in zip(got[term], ref[term])]
        print(f"{term}_gap_by_step:", " ".join(f"{g:.3e}" for g in by_step),
              flush=True)
        out[term + "_gap_max"] = float(max(by_step))
    for what in ("moment_norm", "delta_norm"):
        r = ref[what]
        med = float(np.median(list(r.values())))
        worst = max(r, key=lambda n: abs(got[what][n] - r[n]) / max(r[n], med))
        print(f"{what}_worst_leaf: {worst}", flush=True)
        out[what + "_gap_worst_leaf"] = float(
            abs(got[what][worst] - r[worst]) / max(r[worst], med))
    whole = {k: float(np.sqrt(sum(v * v for v in d["moment_norm"].values())))
             for k, d in (("got", got), ("ref", ref))}
    out["moment_norm_gap_global"] = abs(whole["got"] - whole["ref"]) / whole["ref"]
    power = {k: float(np.sqrt(sum(v * v for v in d["second_moment_root"].values())))
             for k, d in (("got", got), ("ref", ref))}
    out["grad_power_gap_global"] = abs(power["got"] - power["ref"]) / power["ref"]
    # a count next to the mean flips on rounding; a bias never moved, or
    # moved by the optimizer, reads about a half or lies off the rule's grid
    moved = np.asarray(got["bias"], np.float64) / speed
    want = np.asarray(ref["bias"], np.float64) / speed
    out["bias_sign_flip_share"] = float(np.mean(np.abs(moved - want) > 0.5))
    out["bias_off_grid_max"] = float(np.max(np.abs(moved - np.rint(moved))))
    flips = [np.abs(np.asarray(a, np.float64) - np.asarray(b, np.float64)).sum()
             / (2.0 * np.asarray(b, np.float64).sum())
             for a, b in zip(got["expert_rows"], ref["expert_rows"])]
    out["route_flip_share"] = float(max(flips))
    return out

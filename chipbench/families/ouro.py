"""Family ``ouro``: builds ``OuroForCausalLM`` + ``GenerativeServer`` from a
configuration file and a mix's ``system`` block.  The window, the sender and
the warm-up are ``families/llama.py``'s own code: that file is loaded here and
its ``Cell`` subclassed, with ``build``, ``_slots`` and ``check`` overridden.

``check`` compares LOGITS AT THE PUBLISHED WIDTHS, OF WHAT THE TIMED PATH
PRODUCED: a seeded sample of finished requests, the longest among them, each
run once through ``references/ouro.py`` (prompt then served tokens, float32,
every pass of every layer over the whole sequence, no cache); at every served
token the gap between the reference's best logit and its logit of the served
token, in units of that position's logit standard deviation.  So the prefill's
hand-over of a pass's rows into that pass's blocks and every decode step's
four passes through them have to agree with the reference's cache-less pass.
Two rows decide, each where the mix gives it a limit: the widest gap
(``check.gap_limit``) and the mean gap (``check.gap_mean_limit``).

With ``--control 1`` two references take the program's place in turn, and their
rows are shown as readings (beside ``bf16.*``: the float32 reference with every
product's operands and result rounded to bfloat16, the precision the
configuration states; it is held to nothing and says how far bfloat16 itself
carries a served token from the float32 reference over ``passes x layers``
applications): the float8 reference (``control.*``) and the
float32 reference whose pass ``t`` attends the keys and values of pass ``t -
1`` (``control_shared.*``: a server that shares one cache between passes).
``control.passes_every_limit`` and ``control_shared.passes_every_limit`` are 1
where none of the limits refuses that control, and are held to 0: a control run
is ``correct`` only if the sound side passes AND both controls come out as not
correct.

Only this file knows the program's names for this family.  The weights are the
benchmark's: made from the seed by the reference's initialiser, one donated
jitted call a layer, and put into the net's parameters under the leaf names the
reference gives them.
"""
from __future__ import annotations

import importlib.util
import os

import numpy as np

_spec = importlib.util.spec_from_file_location(
    "chipbench_family_ouro_base",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "llama.py"))
_llama = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_llama)

#: the engine keeps the programs' names whatever the model
PROGRAMS = _llama.PROGRAMS


class Cell(_llama.Cell):
    programs = PROGRAMS

    def build(self, phase, _requests):
        import jax
        import mxnet_tpu as mx
        from mxnet_tpu import nd
        from mxnet_tpu.models.ouro import OuroConfig, OuroForCausalLM
        from mxnet_tpu.serving import GenerativeServer, ServerConfig

        cfg, sy = self.cfg, self.sys
        with phase("weights"):
            net = OuroForCausalLM(OuroConfig(
                hidden_size=cfg["hidden_size"],
                intermediate_size=cfg["intermediate_size"],
                num_layers=cfg["num_hidden_layers"],
                num_heads=cfg["num_attention_heads"],
                num_kv_heads=cfg["num_key_value_heads"],
                head_dim=cfg["head_dim"], vocab_size=cfg["vocab_size"],
                max_seq_len=sy["max_length"], rope_theta=cfg["rope_theta"],
                norm_eps=cfg["rms_norm_eps"],
                total_ut_steps=cfg["total_ut_steps"],
                early_exit_threshold=cfg["early_exit_threshold"]))
            assert not cfg["tie_word_embeddings"] and cfg["rope_scaling"] is None
            assert cfg["hidden_act"] == "silu" and not cfg["use_sliding_window"]
            net.cast(cfg["torch_dtype"])
            net.collect_params().setattr("grad_req", "null")

            class _Leave(mx.init.Initializer):
                """Parameters are born as device zeros and filled below."""

                def __call__(self, desc, arr):
                    pass

            net.initialize(_Leave())
            slots = self._slots(net)
            ref, dtype = self.ref, cfg["torch_dtype"]

            # one program for the layers, the layer index traced; each call
            # takes over the zeros' memory
            def fill_top(old, key):
                del old
                return ref.init_top(ref.top_key(key), cfg, dtype)

            def fill_layer(old, key, l):
                del old
                return ref.init_layer(ref.layer_key(key, l), cfg, dtype)

            key = jax.random.PRNGKey(self.seed % (2 ** 31 - 1))
            fill_top = jax.jit(fill_top, donate_argnums=0)
            fill_layer = jax.jit(fill_layer, donate_argnums=0)
            groups = ["top"] + [f"l{l}" for l in range(cfg["num_hidden_layers"])]
            n_params = 0
            for l, g in enumerate(groups):
                mine = {n.split(".", 1)[1]: p for n, p in slots.items()
                        if n.split(".", 1)[0] == g}
                old = {n: p.data()._data for n, p in mine.items()}
                home = {n: a.sharding for n, a in old.items()}
                new = fill_top(old, key) if g == "top" else \
                    fill_layer(old, key, jax.numpy.int32(l - 1))
                assert sorted(new) == sorted(mine), (g, sorted(new), sorted(mine))
                for n, p in mine.items():   # committed, as parameters are born
                    p.set_data(nd.NDArray(jax.device_put(
                        new[n].reshape(old[n].shape), home[n])))
                n_params += sum(int(np.prod(a.shape)) for a in new.values())
            jax.block_until_ready(new)
            del old, new
            self.n_params = n_params
        with phase("server"):
            self.net = net
            self.server = GenerativeServer(net, ServerConfig(
                max_batch=sy["max_batch"], max_length=sy["max_length"],
                min_length=sy["min_length"], num_slots=sy["num_slots"],
                kv_mode="paged", block_size=sy["block_size"],
                num_blocks=sy["num_blocks"],
                queue_capacity=sy["queue_capacity"]))
            self.server.start()
        with phase("warm_up"):
            self._warm_up()

    @staticmethod
    def _slots(net):
        """Reference leaf name -> the program's Parameter."""
        out = {"top.emb": net.embed_tokens.weight, "top.norm": net.norm.weight,
               "top.head": net.lm_head.weight,
               "top.gate_w": net.early_exit_gate.weight,
               "top.gate_b": net.early_exit_gate.bias}
        for l, lr in enumerate(net.layers):
            out.update({f"l{l}.{n}": getattr(lr, n) for n in lr._names})
        return out

    def check(self, control):
        """This file's docstring.  Returns rows (name, value, limit)."""
        done = [r for r in self._rows if r["finished"]]
        chk = self.mix["check"]
        bad = 0
        for r in done:
            res = r["_rec"]["req"].future.result()
            p = r["_rec"]["item"]["prompt"]
            if res.shape != (len(p) + r["n_out"],) or not (res[:len(p)] == p).all():
                bad += 1
        out = [("answers_malformed", float(bad), 0.0)]
        if not done:
            return out + [("requests_finished", 0.0, None)]
        # a seeded sample with the longest request in it
        rng = np.random.default_rng([self.seed % (2 ** 63), 7])
        order = sorted(range(len(done)),
                       key=lambda i: -(done[i]["n_prompt"] + done[i]["n_out"]))
        pick = [order[0]] + [int(i) for i in rng.permutation(order[1:])
                             [:max(0, int(chk["requests"]) - 1)]]
        prompts, served = [], []
        for i in pick:
            res, n = done[i]["_rec"]["req"].future.result(), done[i]["n_prompt"]
            prompts.append(res[:n])
            served.append(res[n:])
        max_rows = int(chk["requests"]) * int(self.mix["output_tokens"]["hi"])
        pad = int(chk["pad_tokens"])
        limits = (chk["gap_limit"], chk["gap_mean_limit"])

        def rows(prefix, gaps, limits):
            return [(prefix + "served_logit_gap_max", float(gaps.max()),
                     limits[0]),
                    (prefix + "served_logit_gap_mean", float(gaps.mean()),
                     limits[1])]

        gaps = self.ref.served_gaps(self.cfg, self.seed, prompts, served, pad,
                                    max_rows)
        self.checked_tokens = int(len(gaps))
        out += rows("", gaps, limits)
        out.append(("checked_tokens", float(len(gaps)), None))
        out.append(("sampled_tokens_longest", float(max(
            len(p) + len(s) for p, s in zip(prompts, served))), None))
        if control:
            for prefix, fault in (("control.", "lowp"),
                                  ("control_shared.", "shared_cache")):
                cg = self.ref.served_gaps(self.cfg, self.seed, prompts, served,
                                          pad, max_rows, control=fault)
                held = rows(prefix, cg, limits)
                out += [(name, value, None) for name, value, _l in held]
                out.append((prefix + "passes_every_limit", float(all(
                    value <= limit for _n, value, limit in held
                    if limit is not None)), 0.0))
            bg = self.ref.served_gaps(self.cfg, self.seed, prompts, served, pad,
                                      max_rows, control="bfloat16")
            out += [(name, value, None) for name, value, _l in
                    rows("bf16.", bg, limits)]
        return out

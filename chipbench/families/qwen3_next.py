"""Family ``qwen3_next``: builds ``Qwen3NextForCausalLM`` + ``GenerativeServer``
from a configuration file and a mix's ``system`` block.  The window, the sender
and the warm-up are ``families/llama.py``'s own code: that file is loaded here
and its ``Cell`` subclassed, with ``build``, ``_slots`` and ``check`` overridden.

``check`` compares LOGITS AT THE PUBLISHED WIDTHS, OF WHAT THE TIMED PATH
PRODUCED: a seeded sample of finished requests, the longest among them, each run
once through ``references/qwen3_next.py`` (prompt then served tokens, float32,
the delta rule token by token, dense attention, no cache); at every served token
the gap between the reference's best logit and its logit of the served token, in
units of that position's logit standard deviation.  So the chunked scan of the
prefill, the hand-over of a slot's float32 states and convolution rings, and
every decode step through them and the paged K/V have to agree with the
reference's cache-less pass.

As for ``families/lfm2.py``, a top-10-of-512 choice on a margin flips between
bfloat16 and float32 activations, and such a token's logits move by more than
any rounding moves them: the WIDEST gap is a reading (``gap_limit`` null) and
these rows decide, each where the mix gives it a limit:

* ``served_logit_gap_mean`` over the sample (``check.gap_mean_limit``);
* ``served_logit_gap_max_steady``: the widest gap over the STEADY tokens, whose
  choice margin (``combine_weights``: the last expert chosen over the first
  left out, in router logits; the smallest over the layers, in the reference's
  float32 pass) is at least ``check.choice_margin_floor``
  (``check.gap_steady_limit``);
* ``served_logit_gap_share_over_<t>``: the share of all sampled tokens with a
  gap above ``check.gap_share_over`` (``check.gap_share_limit``).

With ``--control 1`` two references take the program's place in turn, and their
rows are shown as readings: the float8 reference (``control.*``) and the float32
reference whose recurrent states are zeroed after each prompt
(``control_state.*``: a server whose prefill did not hand its state over).
``control.passes_every_limit`` and ``control_state.passes_every_limit`` are 1
where none of the limits above refuses that control, and are held to 0: a
control run is ``correct`` only if the sound side passes AND both controls come
out as not correct.

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
    "chipbench_family_qwen3_next_base",
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
        from mxnet_tpu.models.qwen3_next import (Qwen3NextConfig,
                                                 Qwen3NextForCausalLM)
        from mxnet_tpu.serving import GenerativeServer, ServerConfig

        cfg, sy = self.cfg, self.sys
        with phase("weights"):
            net = Qwen3NextForCausalLM(Qwen3NextConfig(
                hidden_size=cfg["hidden_size"],
                num_layers=cfg["num_hidden_layers"],
                full_attention_interval=cfg["full_attention_interval"],
                num_heads=cfg["num_attention_heads"],
                num_kv_heads=cfg["num_key_value_heads"],
                attn_head_dim=cfg["head_dim"],
                partial_rotary_factor=cfg["partial_rotary_factor"],
                linear_num_key_heads=cfg["linear_num_key_heads"],
                linear_num_value_heads=cfg["linear_num_value_heads"],
                linear_key_head_dim=cfg["linear_key_head_dim"],
                linear_value_head_dim=cfg["linear_value_head_dim"],
                linear_conv_kernel_dim=cfg["linear_conv_kernel_dim"],
                num_experts=cfg["router_experts"],
                num_experts_per_tok=cfg["num_experts_per_tok"],
                moe_intermediate_size=cfg["moe_intermediate_size"],
                shared_expert_intermediate_size=cfg[
                    "shared_expert_intermediate_size"],
                norm_topk_prob=cfg["norm_topk_prob"],
                experts_held=tuple(cfg["experts_held"]),
                vocab_size=cfg["vocab_size"], max_seq_len=sy["max_length"],
                rope_theta=cfg["rope_theta"], norm_eps=cfg["rms_norm_eps"]))
            assert cfg["experts_held"][1] == cfg["num_experts"]
            assert cfg["decoder_sparse_step"] == 1 and not cfg["mlp_only_layers"]
            assert not cfg["tie_word_embeddings"] and cfg["rope_scaling"] is None
            assert cfg["hidden_act"] == "silu"
            net.cast(cfg["torch_dtype"])
            net.collect_params().setattr("grad_req", "null")

            class _Leave(mx.init.Initializer):
                """Parameters are born as device zeros and filled below."""

                def __call__(self, desc, arr):
                    pass

            net.initialize(_Leave())
            slots = self._slots(net)
            ref, dtype = self.ref, cfg["torch_dtype"]

            # one program per kind of layer, the layer index traced; each
            # call takes over the zeros' memory
            def fill_top(old, key):
                del old
                return ref.init_top(ref.top_key(key), cfg, dtype)

            def fill_layer(old, key, l, kind):
                del old
                return ref.init_layer(ref.layer_key(key, l), cfg, dtype, kind)

            key = jax.random.PRNGKey(self.seed % (2 ** 31 - 1))
            fill_top = jax.jit(fill_top, donate_argnums=0)
            fill_layer = jax.jit(fill_layer, donate_argnums=0, static_argnums=3)
            groups = ["top"] + [f"l{l}" for l in range(cfg["num_hidden_layers"])]
            n_params = 0
            for l, g in enumerate(groups):
                mine = {n.split(".", 1)[1]: p for n, p in slots.items()
                        if n.split(".", 1)[0] == g}
                old = {n: p.data()._data for n, p in mine.items()}
                home = {n: a.sharding for n, a in old.items()}
                new = fill_top(old, key) if g == "top" else \
                    fill_layer(old, key, jax.numpy.int32(l - 1),
                               ref.layer_kind(cfg, l - 1))
                assert sorted(new) == sorted(mine), (g, sorted(new), sorted(mine))
                for n, p in mine.items():   # committed, as parameters are born
                    p.set_data(nd.NDArray(jax.device_put(new[n], home[n])))
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
               "top.head": net.lm_head.weight}
        for l, lr in enumerate(net.layers):
            out.update({f"l{l}.{n}": p for n, p in lr._reg_params.items()})
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
        gaps, margin = self.ref.served_gaps(self.cfg, self.seed, prompts, served,
                                            pad, max_rows)
        self.checked_tokens = int(len(gaps))
        steady = margin >= chk["choice_margin_floor"]
        over = chk["gap_share_over"]

        def rows(prefix, gaps, limits):
            # no steady token: nothing was held to the limit, so it fails
            widest = float(gaps[steady].max()) if steady.any() \
                else float("inf")
            return [(prefix + "served_logit_gap_max", float(gaps.max()),
                     limits[0]),
                    (prefix + "served_logit_gap_mean", float(gaps.mean()),
                     limits[1]),
                    (prefix + "served_logit_gap_max_steady", widest, limits[2]),
                    (prefix + f"served_logit_gap_share_over_{over}",
                     float((gaps > over).mean()), limits[3])]

        limits = (chk["gap_limit"], chk["gap_mean_limit"],
                  chk["gap_steady_limit"], chk["gap_share_limit"])
        out += rows("", gaps, limits)
        out.append(("steady_token_share", float(steady.mean()), None))
        out.append(("checked_tokens", float(len(gaps)), None))
        out.append(("sampled_tokens_longest", float(max(
            len(p) + len(s) for p, s in zip(prompts, served))), None))
        if control:
            for prefix, kind in (("control.", "lowp"),
                                 ("control_state.", "lost_state")):
                cg, _m = self.ref.served_gaps(self.cfg, self.seed, prompts, served,
                                              pad, max_rows, control=kind)
                held = rows(prefix, cg, limits)
                out += [(name, value, None) for name, value, _l in held]
                out.append((prefix + "passes_every_limit", float(all(
                    value <= limit for _n, value, limit in held
                    if limit is not None)), 0.0))
        return out

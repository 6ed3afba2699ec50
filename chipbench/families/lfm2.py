"""Family ``lfm2``: builds ``Lfm2MoeForCausalLM`` + ``GenerativeServer`` from a
configuration file and a mix's ``system`` block.  The window, the sender, the
warm-up and the check are ``families/llama.py``'s own code: that file is
loaded here and its ``Cell`` subclassed, with ``build`` (another net, and
weights that differ in kind from layer to layer) and ``_slots`` overridden.

``check`` is that file's too, with three more rows.  A routed model's served
tokens differ from a dense model's in one way: where the last expert chosen
and the first one left out score nearly alike, bfloat16 activations choose
another expert than float32 ones, and that one token's logits move by more
than any rounding moves them.  So the WIDEST gap of a sound run reaches the
float8 control's and stays a reading (the mix's ``gap_limit`` is null).  What
decides ``correct`` (readings in PERF.md section 4, PR 26):

* ``served_logit_gap_mean`` over the sample, held to ``check.gap_mean_limit``;
* ``served_logit_gap_max_steady``: the widest gap over the STEADY tokens,
  those whose choice margin (``references/lfm2.py`` ``combine_weights``; the
  smallest over the expert layers, in the reference's float32 pass) is at
  least ``check.choice_margin_floor``, held to ``check.gap_steady_limit``:
  it sees a single wrong token, which the mean cannot;
* ``served_logit_gap_share_over_half``: the share of all sampled tokens with
  a gap above 0.5, held to ``check.gap_over_half_share_limit``: a dozen wrong
  tokens among the unsteady ones, which neither of the others sees.

Only this file knows the program's names for this family.  The weights are the
benchmark's: made from the seed by ``references/lfm2.py``'s initialiser, one
donated jitted call a layer, and put into the net's parameters under the leaf
names the reference gives them (an expert bank is stacked (experts, in, out)).
"""
from __future__ import annotations

import importlib.util
import os

import numpy as np

_spec = importlib.util.spec_from_file_location(
    "chipbench_family_lfm2_base",
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
        from mxnet_tpu.models.lfm2 import Lfm2MoeConfig, Lfm2MoeForCausalLM
        from mxnet_tpu.serving import GenerativeServer, ServerConfig

        cfg, sy = self.cfg, self.sys
        with phase("weights"):
            net = Lfm2MoeForCausalLM(Lfm2MoeConfig(
                hidden_size=cfg["hidden_size"],
                intermediate_size=cfg["intermediate_size"],
                moe_intermediate_size=cfg["moe_intermediate_size"],
                num_layers=cfg["num_hidden_layers"],
                num_dense_layers=cfg["num_dense_layers"],
                layer_types=cfg["layer_types"],
                num_heads=cfg["num_attention_heads"],
                num_kv_heads=cfg["num_key_value_heads"],
                vocab_size=cfg["vocab_size"], max_seq_len=sy["max_length"],
                rope_theta=cfg["rope_parameters"]["rope_theta"],
                norm_eps=cfg["norm_eps"], conv_L_cache=cfg["conv_L_cache"],
                num_experts=cfg["num_experts"],
                num_experts_per_tok=cfg["num_experts_per_tok"],
                norm_topk_prob=cfg["norm_topk_prob"],
                use_expert_bias=cfg["use_expert_bias"],
                routed_scaling_factor=cfg["routed_scaling_factor"]))
            assert net.config.head_dim == cfg["head_dim"]
            assert cfg["tie_word_embeddings"] and not cfg["conv_bias"]
            net.cast(cfg["torch_dtype"])
            net.collect_params().setattr("grad_req", "null")

            class _Leave(mx.init.Initializer):
                """Parameters are born as device zeros and filled below."""

                def __call__(self, desc, arr):
                    pass

            net.initialize(_Leave())
            slots = self._slots(net)
            ref, dtype = self.ref, cfg["torch_dtype"]

            # one program per kind of layer (operator x feed-forward), the
            # layer index traced; each call takes over the zeros' memory
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

    def check(self, control):
        """``families/llama.py``'s check, and three more rows over the same
        sample (this file's docstring)."""
        ref, kept = self.ref, {}

        class _Tap:
            """Keeps the comparison's gaps and margins as the check reads
            them."""

            @staticmethod
            def served_gaps(*args, **kw):
                gaps, kept["margin"] = ref.served_gaps(
                    *args, with_margin=True, **kw)
                kept["control" if kw.get("lowp_control") else "sound"] = gaps
                return gaps

        self.ref = _Tap
        try:
            rows = super().check(control)
        finally:
            self.ref = ref
        if "sound" not in kept:
            return rows
        chk = self.mix["check"]
        steady = kept["margin"] >= chk["choice_margin_floor"]

        def more(prefix, gaps, limits):
            # no steady token: nothing was held to the limit, so it fails
            widest = float(gaps[steady].max()) if steady.any() \
                else float("inf")
            return [(prefix + "served_logit_gap_mean", float(gaps.mean()),
                     limits[0]),
                    (prefix + "served_logit_gap_max_steady", widest,
                     limits[1]),
                    (prefix + "served_logit_gap_share_over_half",
                     float((gaps > 0.5).mean()), limits[2])]

        named = {"served_logit_gap_mean", "control.served_logit_gap_mean"}
        rows = [r for r in rows if r[0] not in named]
        rows += more("", kept["sound"], (chk["gap_mean_limit"],
                                         chk["gap_steady_limit"],
                                         chk["gap_over_half_share_limit"]))
        rows.append(("steady_token_share", float(steady.mean()), None))
        if "control" in kept:
            rows += more("control.", kept["control"], (None, None, None))
        return rows

    @staticmethod
    def _slots(net):
        """Reference leaf name -> the program's Parameter."""
        out = {"top.emb": net.embed_tokens.weight, "top.norm": net.norm.weight}
        for l, lr in enumerate(net.layers):
            out.update({f"l{l}.{n}": p for n, p in lr._reg_params.items()})
        return out

"""Family ``nemotron_h``: builds ``NemotronHForCausalLM`` + ``GenerativeServer``
from a configuration file and a mix's ``system`` block.  The window, the sender
and the warm-up are ``families/llama.py``'s own code, and the check is
``families/qwen3_next.py``'s (that file is loaded here and its ``Cell``
subclassed, with ``build`` and ``_slots`` overridden).

``check`` compares LOGITS AT THE PUBLISHED WIDTHS, OF WHAT THE TIMED PATH
PRODUCED: a seeded sample of finished requests, the longest among them, each run
once through ``references/nemotron_h.py`` (prompt then served tokens, float32,
the state-space recurrence token by token, dense attention, no cache, the held
bank walked in blocks of experts); at every served token the gap between the
reference's best logit and its logit of the served token, in units of that
position's logit standard deviation.  So the chunked scan of the prefill, the
hand-over of a slot's float32 states and convolution rings, and every decode
step through them and the paged K/V have to agree with the reference's
cache-less pass.

A top-22-of-512 choice on a margin flips between bfloat16 and float32
activations, and such a token's logits move by more than any rounding moves
them: the WIDEST gap is a reading (``gap_limit`` null) and the rows that decide
are ``families/qwen3_next.py``'s, each where the mix gives it a limit: the mean
gap (``check.gap_mean_limit``), the widest gap over the STEADY tokens
(``check.gap_steady_limit``; the choice margin, the 22nd router logit over the
23rd in the reference's float32 pass, at least ``check.choice_margin_floor`` in
all five expert layers) and the share of sampled tokens with a gap above
``check.gap_share_over`` (``check.gap_share_limit``).  With ``--control 1`` the
float8 reference (``control.*``) and the float32 reference whose recurrent
states are zeroed after each prompt (``control_state.*``) take the program's
place in turn; a control run is ``correct`` only if the sound side passes AND
both controls come out as not correct.

Only this file knows the program's names for this family, and that the program
serves a mixer and the expert layer behind it as ONE layer: the reference's
leaves of pattern position ``l`` go to the served layer that holds that letter.
The model module is imported before any weight is made, so a program without
it fails at once.  The weights are the benchmark's: made from the seed by the
reference's initialiser, one donated jitted call a letter of the pattern.
"""
from __future__ import annotations

import importlib.util
import os

import numpy as np

_spec = importlib.util.spec_from_file_location(
    "chipbench_family_nemotron_h_base",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "qwen3_next.py"))
_qwen = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_qwen)

#: the engine keeps the programs' names whatever the model
PROGRAMS = _qwen.PROGRAMS


class Cell(_qwen.Cell):
    programs = PROGRAMS

    def build(self, phase, _requests):
        import jax
        import mxnet_tpu as mx
        from mxnet_tpu import nd
        from mxnet_tpu.models.nemotron_h import (NemotronHConfig,
                                                 NemotronHForCausalLM)
        from mxnet_tpu.serving import GenerativeServer, ServerConfig

        cfg, sy = self.cfg, self.sys
        with phase("weights"):
            net = NemotronHForCausalLM(NemotronHConfig(
                hidden_size=cfg["hidden_size"],
                pattern=cfg["hybrid_override_pattern"],
                mamba_num_heads=cfg["mamba_num_heads"],
                mamba_head_dim=cfg["mamba_head_dim"],
                ssm_state_size=cfg["ssm_state_size"],
                n_groups=cfg["n_groups"], conv_kernel=cfg["conv_kernel"],
                num_heads=cfg["num_attention_heads"],
                num_kv_heads=cfg["num_key_value_heads"],
                attn_head_dim=cfg["head_dim"],
                num_experts=cfg["router_experts"],
                num_experts_per_tok=cfg["num_experts_per_tok"],
                moe_latent_size=cfg["moe_latent_size"],
                moe_intermediate_size=cfg["moe_intermediate_size"],
                shared_expert_intermediate_size=cfg[
                    "moe_shared_expert_intermediate_size"],
                routed_scaling_factor=cfg["routed_scaling_factor"],
                norm_topk_prob=cfg["norm_topk_prob"],
                experts_held=tuple(cfg["experts_held"]),
                vocab_size=cfg["vocab_size"], max_seq_len=sy["max_length"],
                norm_eps=cfg["norm_eps"]))
            pattern = cfg["hybrid_override_pattern"]
            assert cfg["experts_held"][1] == cfg["n_routed_experts"]
            assert len(pattern) == cfg["num_hidden_layers"]
            assert cfg["expand"] * cfg["hidden_size"] \
                == cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
            assert cfg["mlp_hidden_act"] == "relu2" and cfg["use_conv_bias"]
            assert cfg["n_shared_experts"] == 1 and cfg["n_group"] == 1
            assert not (cfg["tie_word_embeddings"] or cfg["attention_bias"]
                        or cfg["mamba_proj_bias"] or cfg["mlp_bias"])
            assert cfg["mamba_hidden_act"] == "silu"
            assert cfg["layer_norm_epsilon"] == cfg["norm_eps"]
            net.cast(cfg["torch_dtype"])
            net.collect_params().setattr("grad_req", "null")

            class _Leave(mx.init.Initializer):
                """Parameters are born as device zeros and filled below."""

                def __call__(self, desc, arr):
                    pass

            net.initialize(_Leave())
            slots = self._slots(net)
            ref, dtype = self.ref, cfg["torch_dtype"]

            # one program per kind of layer, the pattern position traced;
            # each call takes over the zeros' memory
            def fill_top(old, key):
                del old
                return ref.init_top(ref.top_key(key), cfg, dtype)

            def fill_layer(old, key, l, kind):
                del old
                return ref.init_layer(ref.layer_key(key, l), cfg, dtype, kind)

            key = jax.random.PRNGKey(self.seed % (2 ** 31 - 1))
            fill_top = jax.jit(fill_top, donate_argnums=0)
            fill_layer = jax.jit(fill_layer, donate_argnums=0, static_argnums=3)
            groups = ["top"] + [f"l{l}" for l in range(len(pattern))]
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
        """Reference leaf name (``l<pattern position>.<leaf>``) -> the
        program's Parameter: a served layer holds its mixer's letter and the
        ``E`` behind it, if one follows."""
        out = {"top.emb": net.embed_tokens.weight, "top.norm": net.norm.weight,
               "top.head": net.lm_head.weight}
        expert_leaves = ("ffn_norm", "router", "expert_bias", "latent_down",
                         "latent_up", "w_up", "w_down", "shared_up",
                         "shared_down")
        at = 0
        for lr, (_mixer, experts) in zip(net.layers, net.config.units):
            for n, p in lr._reg_params.items():
                out[f"l{at + (n in expert_leaves)}.{n}"] = p
            at += 1 + experts
        return out

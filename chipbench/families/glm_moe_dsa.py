"""Family ``glm_moe_dsa``: builds ``GlmMoeDsaForCausalLM`` + ``GenerativeServer``
from a configuration file and a mix's ``system`` block.  The window, the sender
and the warm-up are ``families/llama.py``'s own code: that file is loaded here
and its ``Cell`` subclassed, with ``build``, ``_slots`` and ``check`` overridden.

``check`` compares LOGITS AT THE PUBLISHED WIDTHS, OF WHAT THE TIMED PATH
PRODUCED: a seeded sample of finished requests, the longest among them, each run
once through ``references/glm_moe_dsa.py`` (prompt then served tokens, float32,
expanded attention, no cache); at every served token the gap between the
reference's best logit and its logit of the served token, in units of that
position's logit standard deviation.  So the prefill (absorbed attention over the
rows it selected, in query tiles), the hand-over into the latent and index-key
pools and every decode step's selection from the cache have to agree with the
reference's cache-less pass.

As for ``families/lfm2.py``, a top-8-of-256 choice on a margin flips between
bfloat16 and float32 activations, and such a token's logits move by more than
any rounding moves them (so may the 2,048th key, by much less): the WIDEST gap
is a reading (``gap_limit`` null) and these rows decide (readings in PERF.md
section 4, PR 32):

* ``served_logit_gap_mean`` over the sample, held to ``check.gap_mean_limit``;
* ``served_logit_gap_max_steady``: the widest gap over the STEADY tokens, whose
  choice margin (``references/glm_moe_dsa.py`` ``combine_weights``; the smallest
  over the expert layers, in the reference's float32 pass) is at least
  ``check.choice_margin_floor``, held to ``check.gap_steady_limit`` (null in
  the cell, so a reading there: sound and control lie a factor of 1.3 apart);
* ``served_logit_gap_share_over_<t>``: the share of all sampled tokens with a gap
  above ``check.gap_share_over``, held to ``check.gap_share_limit``;
* ``selection_miss_max``: with seeded weights attention is near uniform, and a
  wrong selection hardly moves a logit.  The server keeps, a request, what each
  layer of its LAST decode step selected (``Request.selected``, one small fetch
  at the request's end); the reference says what its own layers select at that
  row.  The miss is the share of the reference's set that the server did not
  read, the largest over the sampled requests and the layers, held to
  ``check.selection_miss_limit``: a sound run misses keys on a margin between
  bfloat16 and float32 scores; most recent keys instead of the indexer's, a
  stale index key or another order of ties miss most of the set.

With ``--control 1`` two references take the program's place in turn, and their
rows are shown as readings: the float8 reference (``control.*``: its tokens, and
its own selection at those rows), and the float32 reference selecting the
``index_topk`` MOST RECENT positions (``control_recent.*``).
``control.passes_every_limit`` and ``control_recent.passes_every_limit`` are 1
where none of the limits above refuses that control, and are held to 0: a control
run is ``correct`` only if the sound side passes AND both controls come out as
not correct.

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
    "chipbench_family_glm_base",
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
        from mxnet_tpu.models.glm_moe_dsa import (GlmMoeDsaConfig,
                                                  GlmMoeDsaForCausalLM)
        from mxnet_tpu.serving import GenerativeServer, ServerConfig

        cfg, sy = self.cfg, self.sys
        with phase("weights"):
            net = GlmMoeDsaForCausalLM(GlmMoeDsaConfig(
                hidden_size=cfg["hidden_size"],
                intermediate_size=cfg["intermediate_size"],
                moe_intermediate_size=cfg["moe_intermediate_size"],
                num_layers=cfg["num_hidden_layers"],
                first_k_dense=cfg["first_k_dense_replace"],
                num_heads=cfg["num_attention_heads"],
                q_lora_rank=cfg["q_lora_rank"],
                kv_lora_rank=cfg["kv_lora_rank"],
                qk_nope_head_dim=cfg["qk_nope_head_dim"],
                qk_rope_head_dim=cfg["qk_rope_head_dim"],
                v_head_dim=cfg["v_head_dim"],
                index_n_heads=cfg["index_n_heads"],
                index_head_dim=cfg["index_head_dim"],
                index_topk=cfg["index_topk"],
                num_experts=cfg["router_experts"],
                num_experts_per_tok=cfg["num_experts_per_tok"],
                n_shared_experts=cfg["n_shared_experts"],
                norm_topk_prob=cfg["norm_topk_prob"],
                routed_scaling_factor=cfg["routed_scaling_factor"],
                experts_held=tuple(cfg["experts_held"]),
                vocab_size=cfg["vocab_size"], max_seq_len=sy["max_length"],
                rope_theta=cfg["rope_parameters"]["rope_theta"],
                norm_eps=cfg["rms_norm_eps"]))
            assert cfg["experts_held"][1] == cfg["n_routed_experts"]
            assert cfg["scoring_func"] == "sigmoid" and cfg["n_group"] == 1
            assert not cfg["tie_word_embeddings"] and not cfg["attention_bias"]
            assert cfg["qk_head_dim"] == cfg["qk_nope_head_dim"] \
                + cfg["qk_rope_head_dim"] and cfg["rope_interleave"]
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
        prompts, served, at, read = [], [], [], []
        for i in pick:
            req = done[i]["_rec"]["req"]
            res, n = req.future.result(), done[i]["n_prompt"]
            prompts.append(res[:n])
            served.append(res[n:])
            # the last step's query: the last token but one
            pos, sel = req.selected if req.selected is not None \
                else (len(res) - 2, None)
            at.append(pos)
            read.append(sel)
        max_rows = int(chk["requests"]) * int(self.mix["output_tokens"]["hi"])
        pad = int(chk["pad_tokens"])
        gaps, info = self.ref.served_gaps(
            self.cfg, self.seed, prompts, served, pad, max_rows, selected_at=at)
        self.checked_tokens = int(len(gaps))
        steady = info["margin"] >= chk["choice_margin_floor"]
        over = chk["gap_share_over"]
        want = info["chosen"]                   # (layers, requests, pad) bool

        def miss(selected):
            """The largest share, over requests and layers, of the
            reference's set that ``selected(i)`` (layers, pad) bool lacks."""
            worst = 0.0
            for i in range(len(pick)):
                got = selected(i)
                if got is None:
                    return float("inf")     # nothing was kept to compare
                n = want[:, i].sum(axis=-1)
                worst = max(worst, float(
                    ((want[:, i] & ~got).sum(axis=-1) / np.maximum(n, 1)).max()))
            return worst

        def served_set(i):
            if read[i] is None:
                return None
            got = np.zeros(want[:, i].shape, bool)
            for l, row in enumerate(read[i]):
                got[l, row[row >= 0]] = True
            return got

        def rows(prefix, gaps, missed, limits):
            # no steady token: nothing was held to the limit, so it fails
            widest = float(gaps[steady].max()) if steady.any() \
                else float("inf")
            return [(prefix + "served_logit_gap_max", float(gaps.max()),
                     limits[0]),
                    (prefix + "served_logit_gap_mean", float(gaps.mean()),
                     limits[1]),
                    (prefix + "served_logit_gap_max_steady", widest,
                     limits[2]),
                    (prefix + f"served_logit_gap_share_over_{over}",
                     float((gaps > over).mean()), limits[3]),
                    (prefix + "selection_miss_max", missed, limits[4])]

        limits = (chk["gap_limit"], chk["gap_mean_limit"],
                  chk["gap_steady_limit"], chk["gap_share_limit"],
                  chk["selection_miss_limit"])
        out += rows("", gaps, miss(served_set), limits)
        out.append(("steady_token_share", float(steady.mean()), None))
        out.append(("checked_tokens", float(len(gaps)), None))
        out.append(("sampled_tokens_longest", float(max(
            len(p) + len(s) for p, s in zip(prompts, served))), None))
        out.append(("selected_share_at_sampled_rows", float(np.mean(
            [want[0, i].sum() / (at[i] + 1) for i in range(len(pick))])), None))
        if control:
            for prefix, kw in (("control.", {}),
                               ("control_recent.", {"select_control": "recent"})):
                cg, cinfo = self.ref.served_gaps(
                    self.cfg, self.seed, prompts, served, pad, max_rows,
                    lowp_control=True, selected_at=at, **kw)
                held = rows(prefix, cg,
                            miss(lambda i: cinfo["control_chosen"][:, i]), limits)
                out += [(name, value, None) for name, value, _l in held]
                out.append((prefix + "passes_every_limit", float(all(
                    value <= limit for _n, value, limit in held
                    if limit is not None)), 0.0))
        return out

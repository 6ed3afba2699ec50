"""Family ``sdar``: builds ``SdarMoeForCausalLM`` + ``GenerativeServer`` from a
configuration file and a mix's ``system`` block.  The window, the sender and
the warm-up are ``families/llama.py``'s own code: that file is loaded here and
its ``Cell`` subclassed, with ``build``, ``_slots`` and ``check`` overridden.

``check`` compares LOGITS AT THE PUBLISHED WIDTHS, OF WHAT THE TIMED PATH
PRODUCED.  A block decoder commits the positions of a block in any order, so a
served token alone does not say what the pass that committed it saw.  The
server keeps, a request, every commit ``(position, token, the block's pass)``
(``Request.commits``), and ``references/sdar.py`` ``rebuild`` lays each pass's
block out as that pass saw it, behind the final tokens of the blocks before:
rows of one masked cache-less forward a request.  Per committed token, at the
pass that committed it, the gap is the reference's best logit at that row less
its logit of the served token, in logit standard deviations.  So prefill, then
blocks through the paged cache, have to agree with the reference's cache-less
pass: a block whose keys and values were left by the wrong pass, or a causal
order inside a block, moves every later row's logits.

As for ``families/lfm2.py``, a top-8-of-128 choice on a margin flips between
bfloat16 and float32 activations, and such a token's logits move by more than
any rounding moves them: the WIDEST gap is a reading (``gap_limit`` null), and
three rows decide (readings in PERF.md section 4, PR 30):

* ``served_logit_gap_mean`` over the sample, held to ``check.gap_mean_limit``;
* ``served_logit_gap_max_steady``: the widest gap over the STEADY tokens, whose
  choice margin (``references/sdar.py`` ``combine_weights``: router logits, the
  smallest over the layers, the reference's float32 pass) is at least
  ``check.choice_margin_floor``, held to ``check.gap_steady_limit``;
* ``served_logit_gap_share_over_<t>``: the share of all sampled tokens with a
  gap above ``check.gap_share_over`` (0.05 in the cell), held to
  ``check.gap_share_limit``.  ``families/lfm2.py`` reads this share at 0.5;
  here neither a sound run nor the float8 control has ONE token above 0.5 (a
  flipped choice among 8 of 128 renormalised experts moves a logit by less),
  so at 0.5 no limit could lie between the two readings, and a wrong token,
  whose gap is several standard deviations, is above either threshold.

Those three follow the server's own commit record, so a WRONG COMMIT RULE on
the device (the least confident row, position order, another schedule) passes
them: its tokens are still the best of the rows it made.  A fourth row holds
the rule: ``commit_regret_mean``, over the passes that had a choice, of
``references/sdar.py`` ``commit_regret`` (how much more confident, by the
reference's own float32 log-confidences of the block as the pass saw it, a
masked row left behind was than a committed one; 0 where the reference would
have committed the same rows), in units of the block's logit standard
deviation, held to ``check.commit_regret_mean_limit``.  A sound run differs from
the reference in near ties only, so its regret is a rounding's; beside it go
two readings of what the same passes would read had the device taken the
pass's share from the least confident rows or in position order.

Readings beside them: the share above 0.5 and the 99th percentile, the widest
regret, ``passes_reference_commits_otherwise`` (the share of passes in which
the reference's own confidences would have committed other positions than the
server did: near ties of seeded weights) and ``commits_not_the_output``
(requests whose answer is not what their commit record says, held to 0).

With ``--control 1`` the float8 reference takes the program's place (its
tokens, and the rows its own confidences commit) and its rows are shown as
readings; ``control.passes_every_limit`` is 1 where none of the limits above
refuses it and is held to 0, so a control run is ``correct`` only if the sound
side passes AND the control comes out as not correct.

Only this file knows the program's names for this family.
"""
from __future__ import annotations

import importlib.util
import os

import numpy as np

_spec = importlib.util.spec_from_file_location(
    "chipbench_family_sdar_base",
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
        from mxnet_tpu.models.sdar import SdarMoeConfig, SdarMoeForCausalLM
        from mxnet_tpu.serving import GenerativeServer, ServerConfig

        cfg, sy = self.cfg, self.sys
        with phase("weights"):
            net = SdarMoeForCausalLM(SdarMoeConfig(
                hidden_size=cfg["hidden_size"],
                moe_intermediate_size=cfg["moe_intermediate_size"],
                num_layers=cfg["num_hidden_layers"],
                num_heads=cfg["num_attention_heads"],
                num_kv_heads=cfg["num_key_value_heads"],
                head_dim=cfg["head_dim"], vocab_size=cfg["vocab_size"],
                max_seq_len=sy["max_length"], rope_theta=cfg["rope_theta"],
                norm_eps=cfg["rms_norm_eps"], num_experts=cfg["num_experts"],
                num_experts_per_tok=cfg["num_experts_per_tok"],
                norm_topk_prob=cfg["norm_topk_prob"],
                block_length=cfg["block_length"],
                denoising_steps=cfg["denoising_steps"],
                confidence_threshold=cfg["confidence_threshold"],
                mask_token_id=cfg["mask_token_id"]))
            assert not cfg["tie_word_embeddings"] and not cfg["attention_bias"]
            assert not cfg["mlp_only_layers"] and cfg["decoder_sparse_step"] == 1
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
        prompts, commits, unlike = [], [], 0
        for i in pick:
            req = done[i]["_rec"]["req"]
            res, n = req.future.result(), done[i]["n_prompt"]
            said = {pos: tok for pos, tok, _step in req.commits}
            unlike += [said.get(pos) for pos in range(n, len(res))] \
                != res[n:].tolist()
            prompts.append(res[:n])
            commits.append(req.commits)
        out.append(("commits_not_the_output", float(unlike), 0.0))
        gaps, margin, other, regret = self.ref.served_gaps(
            self.cfg, self.seed, prompts, commits, int(chk["pad_tokens"]))
        self.checked_tokens = int(len(gaps))
        steady = margin >= chk["choice_margin_floor"]
        over = chk["gap_share_over"]

        def rows(prefix, gaps, regret, limits):
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
                    # readings: where the gaps lie below the widest
                    (prefix + "served_logit_gap_share_over_half",
                     float((gaps > 0.5).mean()), None),
                    (prefix + "served_logit_gap_p99",
                     float(np.quantile(gaps, 0.99)), None),
                    # no pass had a choice: nothing was held to the limit
                    (prefix + "commit_regret_mean",
                     float(regret[:, 0].mean()) if len(regret)
                     else float("inf"), limits[4]),
                    (prefix + "commit_regret_max",
                     float(regret[:, 0].max()) if len(regret)
                     else float("inf"), None)]

        limits = (chk["gap_limit"], chk["gap_mean_limit"],
                  chk["gap_steady_limit"], chk["gap_share_limit"],
                  chk["commit_regret_mean_limit"])
        out += rows("", gaps, regret, limits)
        for i, rule in ((1, "least_confident"), (2, "position_order")):
            out.append((f"commit_regret_mean_if_{rule}",
                        float(regret[:, i].mean()) if len(regret)
                        else float("inf"), None))
        out.append(("steady_token_share", float(steady.mean()), None))
        out.append(("checked_tokens", float(len(gaps)), None))
        out.append(("passes_reference_commits_otherwise", float(other), None))
        if control:
            cg, _m, _o, cr = self.ref.served_gaps(
                self.cfg, self.seed, prompts, commits, int(chk["pad_tokens"]),
                lowp_control=True)
            held = rows("control.", cg, cr, limits)
            out += [(name, value, None) for name, value, _l in held]
            out.append(("control.passes_every_limit", float(all(
                value <= limit for _n, value, limit in held
                if limit is not None)), 0.0))
        return out

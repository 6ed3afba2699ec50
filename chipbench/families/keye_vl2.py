"""Family ``keye_vl2``: builds ``KeyeVl2ForCausalLM`` + ``GenerativeServer`` from
a configuration file and a mix's ``system`` block.  The window, the sender and
the warm-up are ``families/llama.py``'s own code and the check is
``families/glm_moe_dsa.py``'s (the other family whose layers select what they
read): that file, which loads ``llama.py``, is loaded here and its ``Cell``
subclassed, with ``build`` overridden; ``check`` first waits for the lanes to
finish what was in flight at the window's end (the reference's blocks of a 29k
request take 2.4 GB beside 12 GB resident: a 32k prefill's 1.8 GB of temporaries
at the same moment would not fit).

``check`` (``families/glm_moe_dsa.py``'s docstring says all of it) compares
LOGITS AT THE PUBLISHED WIDTHS, OF WHAT THE TIMED PATH PRODUCED: a seeded sample
of finished requests, the longest among them, each run once through
``references/keye_vl2.py`` (prompt then served tokens, float32, no cache); the
rows that decide are ``served_logit_gap_mean``, the share of tokens over
``check.gap_share_over``, ``selection_miss_max`` (what each layer of a
request's LAST decode step read out of the K/V pools against the reference's own
``topk``) and, this file's own, ``selection_miss_first_layer`` (the same of the
first layer alone, over EVERY finished request: ``Cell._first_layer_rows``; the
row that holds the float8 control apart).  So the prefill (query tiles under the selection's mask), the
hand-over into the K, V and index-key pools and every decode step's selection
from the cache have to agree with the reference's cache-less pass.  With
``--control 1`` the float8 reference and the float32 reference reading the
``topk`` MOST RECENT keys take the program's place in turn, both held to
``passes_every_limit`` 0.

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
    "chipbench_family_keye_base",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "glm_moe_dsa.py"))
_glm = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_glm)

#: the engine keeps the programs' names whatever the model
PROGRAMS = _glm.PROGRAMS


class Cell(_glm.Cell):
    programs = PROGRAMS

    def build(self, phase, _requests):
        import jax
        import mxnet_tpu as mx
        from mxnet_tpu import nd
        from mxnet_tpu.models.keye_vl2 import (KeyeVl2Config,
                                               KeyeVl2ForCausalLM)
        from mxnet_tpu.serving import GenerativeServer, ServerConfig

        cfg, sy = self.cfg, self.sys
        sa = cfg["sa_config"]
        with phase("weights"):
            net = KeyeVl2ForCausalLM(KeyeVl2Config(
                hidden_size=cfg["hidden_size"],
                moe_intermediate_size=cfg["moe_intermediate_size"],
                num_layers=cfg["num_hidden_layers"],
                num_heads=cfg["num_attention_heads"],
                num_kv_heads=cfg["num_key_value_heads"],
                head_dim=cfg["head_dim"],
                mrope_section=tuple(cfg["rope_scaling"]["mrope_section"]),
                index_n_heads=sa["indexer_num_heads"],
                index_head_dim=sa["indexer_head_dim"],
                index_topk=sa["topk"],
                num_experts=cfg["num_experts"],
                num_experts_per_tok=cfg["num_experts_per_tok"],
                norm_topk_prob=cfg["norm_topk_prob"],
                vocab_size=cfg["vocab_size"], max_seq_len=sy["max_length"],
                rope_theta=cfg["rope_theta"], norm_eps=cfg["rms_norm_eps"]))
            assert cfg["decoder_sparse_step"] == 1 and not cfg["mlp_only_layers"]
            assert cfg["num_local_experts"] == cfg["num_experts"]
            assert sa["indexer_num_kv_heads"] == 1
            assert not cfg["tie_word_embeddings"] and not cfg["attention_bias"]
            net.cast(cfg["torch_dtype"])
            net.collect_params().setattr("grad_req", "null")

            class _Leave(mx.init.Initializer):
                """Parameters are born as device zeros and filled below."""

                def __call__(self, desc, arr):
                    pass

            net.initialize(_Leave())
            slots = self._slots(net)
            ref, dtype = self.ref, cfg["torch_dtype"]

            # one program for the top and one for a layer, the layer index
            # traced; each call takes over the zeros' memory
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

    def check(self, control):
        self._stopper.join(timeout=300)
        return self._first_layer_rows(super().check(control), control)

    def _first_layer_rows(self, out, control):
        """``selection_miss_first_layer``: of EVERY finished request, the share
        of the reference's own first-layer set at the request's last decode
        step that the server's step did not read, the largest over them,
        held to ``check.first_layer_miss_limit``.  The first layer's indexer
        reads embeddings alone, so no flipped expert choice of an earlier
        layer stands between the two sets and the reading is the scoring's
        own rounding (``selection_miss_max`` takes the deeper layers, whose
        hidden states carry those flips, and two requests); a control's
        ``passes_every_limit`` is held to this row as well."""
        limit = self.mix["check"]["first_layer_miss_limit"]
        pad = int(self.mix["check"]["pad_tokens"])
        ids, at, got = [], [], []
        for r in self._rows:
            req = r["_rec"]["req"] if r["finished"] else None
            if req is None or req.selected is None:
                continue
            res, (pos, sel) = req.future.result(), req.selected
            ids.append(np.pad(res, (0, pad - len(res))))
            at.append(int(pos))
            got.append(np.zeros(pad, bool))
            got[-1][sel[0][sel[0] >= 0]] = True
        if not ids:
            return out + [("selection_miss_first_layer", float("inf"), limit)]
        want = self.ref.first_layer_selected(self.cfg, self.seed, ids, at)
        n = np.maximum(want.sum(axis=-1), 1)

        def miss(read):
            """(the largest, the median, the smallest) over the requests."""
            each = (want & ~read).sum(axis=-1) / n
            return float(each.max()), float(np.median(each)), float(each.min())

        name = "selection_miss_first_layer"
        most, mid, _least = miss(np.stack(got))
        out = out + [(name, most, limit), (name + "_median", mid, None),
                     ("first_layer_requests", float(len(ids)), None)]
        if control:
            for prefix, kw in (("control.", {"lowp": True}),
                               ("control_recent.", {"select": "recent"})):
                most, _mid, least = miss(self.ref.first_layer_selected(
                    self.cfg, self.seed, ids, at, **kw))
                passes = prefix + "passes_every_limit"
                out = [(n_, float(v and most <= limit), l) if n_ == passes
                       else (n_, v, l) for n_, v, l in out]
                # the smallest: what ONE request of this control reads at least
                out += [(prefix + name, most, None),
                        (prefix + name + "_min", least, None)]
        return out

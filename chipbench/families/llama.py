"""Family ``llama``: builds ``LlamaForCausalLM`` + ``GenerativeServer`` from a
configuration file and a mix's ``system`` block, drives a closed or an open
loop against it, and hands the served tokens to the plain reference.

Only this file knows the program's names.  What it takes from the program:
the system under test (net, server), the request stamps, ``server.stats()``,
``server.in_flight()`` and ``engine.compiled_signatures()``.  The weights are
the benchmark's: made from the seed by ``references/llama.py``'s initialiser
in one donated jitted call and put into the net's parameters.
"""
from __future__ import annotations

import gc
import itertools
import queue
import threading
import time

import numpy as np

#: names of the device programs in the profiler's "XLA Modules" line
PROGRAMS = {"step": r"^jit__step_fn", "prefill": r"^jit__prefill_fn",
            "scatter": r"^jit__scatter_fn"}


class Cell:
    kind = "serve"
    programs = PROGRAMS

    def __init__(self, config, mix, seed, chips, span, reference):
        self.cfg, self.mix, self.seed, self.span = config, mix, seed, span
        self.ref = reference
        self.sys = mix["system"]
        self.server = self.net = None
        self._stopper = None

    # -- set-up ---------------------------------------------------------------
    def build(self, phase, _requests):
        import jax
        import mxnet_tpu as mx
        from mxnet_tpu import nd
        from mxnet_tpu.models.llama import LlamaConfig, LlamaForCausalLM
        from mxnet_tpu.serving import GenerativeServer, ServerConfig

        cfg, sy = self.cfg, self.sys
        with phase("weights"):
            net = LlamaForCausalLM(LlamaConfig(
                hidden_size=cfg["hidden_size"],
                intermediate_size=cfg["intermediate_size"],
                num_layers=cfg["num_hidden_layers"],
                num_heads=cfg["num_attention_heads"],
                num_kv_heads=cfg["num_key_value_heads"],
                vocab_size=cfg["vocab_size"], max_seq_len=sy["max_length"],
                rope_theta=cfg["rope_theta"], rms_eps=cfg["rms_norm_eps"],
                tie_embeddings=cfg["tie_word_embeddings"]))
            net.cast(cfg["torch_dtype"])
            net.collect_params().setattr("grad_req", "null")

            class _Leave(mx.init.Initializer):
                """Parameters are born as device zeros and filled below."""

                def __call__(self, desc, arr):
                    pass

            net.initialize(_Leave())
            slots = self._slots(net)
            ref, dtype = self.ref, cfg["torch_dtype"]

            # one program per kind of block, the layer index traced, so the
            # 16 layers share one compile and the generator's temporaries
            # are one layer's; each call takes over the zeros' memory
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
        m = net.model
        out = {"top.emb": m.embed_tokens.weight, "top.norm": m.norm.weight,
               "top.head": net.lm_head.weight}
        for l, lr in enumerate(m.layers):
            a, f = lr.self_attn, lr.mlp
            out.update({f"l{l}.q": a.q_proj.weight, f"l{l}.k": a.k_proj.weight,
                        f"l{l}.v": a.v_proj.weight, f"l{l}.o": a.o_proj.weight,
                        f"l{l}.gate": f.gate_proj.weight, f"l{l}.up": f.up_proj.weight,
                        f"l{l}.down": f.down_proj.weight,
                        f"l{l}.ln_in": lr.input_layernorm.weight,
                        f"l{l}.ln_post": lr.post_attention_layernorm.weight})
        return out

    def _warm_up(self):
        """One request per prompt-length bucket the mix can reach, two tokens
        each, so that every prefill, scatter and the step program has run."""
        pol = self.server.config.policy
        lo, hi = self.mix["prompt_tokens"]["lo"], self.mix["prompt_tokens"]["hi"]
        want = sorted({pol.length_bucket(n) for n in range(lo, hi + 1, 1)})
        rng = np.random.default_rng(0)
        # twice: the KV pool is born uncommitted and the first program that
        # writes it commits it, so that program compiles once more on its
        # second call (the weights here are committed, as a user's are)
        for _ in range(2):
            futs = []
            for b in want:
                n = max(lo, min(b, hi))
                futs.append(self.server.submit(
                    rng.integers(0, self.cfg["vocab_size"], n, dtype=np.int32), 2))
            for f in futs:
                f.result(timeout=1100)
        eng = self.server.engine
        sigs = eng.compiled_signatures()
        have = sorted(s[2] for s in sigs if s[0] == "prefill")
        if have != want or ("step",) not in sigs:
            raise RuntimeError(f"warm-up reached {sigs}, wanted buckets {want}")

    # -- the window -----------------------------------------------------------
    def _send(self, item, due, t0):
        from mxnet_tpu.serving.protocol import Request

        req = Request(prompt_ids=item["prompt"], max_new_tokens=item["max_new"])
        req.length = len(item["prompt"])
        rec = {"due": due, "req": req, "item": item, "error": None}
        rec["sent"] = time.perf_counter() - t0
        try:
            self.server._submit(req)
        except Exception as exc:   # overload or closed: a failed request
            rec["error"] = repr(exc)
        return rec

    def window(self, seconds, requests):
        eng = self.server.engine
        steps0 = eng.steps
        span = self.span
        sent = []
        t0 = time.perf_counter()
        if self.mix["driver"] == "closed_loop":
            done_q = queue.SimpleQueue()
            it = itertools.cycle(requests)

            def launch():
                rec = self._send(next(it), None, t0)
                sent.append(rec)
                if rec["error"] is None:
                    rec["req"].future.add_done_callback(lambda _f: done_q.put(1))
                else:
                    done_q.put(1)

            with span("bench.submit"):
                for _ in range(int(self.mix["clients"])):
                    launch()
            while True:
                left = t0 + seconds - time.perf_counter()
                if left <= 0:
                    break
                try:
                    with span("bench.wait_completion"):
                        done_q.get(timeout=left)
                except queue.Empty:
                    break
                with span("bench.submit"):
                    launch()
            t_close = time.perf_counter()
            in_flight = self.server.in_flight()
        else:
            for item in requests:
                due = item["due_s"]
                wait = t0 + due - time.perf_counter()
                if wait > 0:
                    with span("bench.wait_due"):
                        time.sleep(wait)
                with span("bench.submit"):
                    sent.append(self._send(item, due, t0))
            with span("bench.wait_completion"):
                for rec in sent:
                    if rec["error"] is None:
                        try:
                            rec["req"].future.result(timeout=300)
                        except Exception as exc:
                            rec["error"] = repr(exc)
            t_close = time.perf_counter()
            in_flight = []
        steps1 = eng.steps
        stats = self.server.stats()

        tokens_done = {r["request_id"]: r["tokens_done"] for r in in_flight
                       if r.get("state") == "decoding"}
        rows = []
        for rec in sent:
            req = rec["req"]
            finished = rec["error"] is None and req.future.done() \
                and req.future.exception() is None and req.t_done <= t_close
            emitted = req.max_new_tokens if finished else \
                tokens_done.get(req.id, 1 if req.t_first is not None
                                and req.t_first <= t_close else 0)
            rows.append({
                "due": rec["due"], "sent": rec["sent"], "error": rec["error"],
                "finished": bool(finished), "emitted": int(emitted),
                "n_prompt": len(rec["item"]["prompt"]),
                "n_out": rec["item"]["max_new"],
                "t_start": None if req.t_start is None else req.t_start - t0,
                "t_first": None if req.t_first is None else req.t_first - t0,
                "t_done": None if req.t_done is None else req.t_done - t0,
                "_rec": rec})
        self._rows = rows
        errors = [r for r in rows if r["error"] is not None]
        # the longest stretch in which no request started, got its first
        # token or finished: a stall of the lanes shows here, with its time
        span_s = t_close - t0
        edges = [0.0] + sorted(t for r in rows for t in
                               (r["t_start"], r["t_first"], r["t_done"])
                               if t is not None and 0.0 <= t <= span_s) + [span_s]
        silence, silence_at = max((b - a, a) for a, b in zip(edges, edges[1:]))
        closed = self.mix["driver"] == "closed_loop"
        return {
            "kind": "serve", "driver": self.mix["driver"], "t0_abs": t0,
            "window_s": t_close - t0 if closed else float(seconds),
            "drained_s": t_close - t0,
            "requests": [{k: v for k, v in r.items() if k != "_rec"} for r in rows],
            "attempted": len(rows), "failed": len(errors) + int(stats["failed"]),
            "ticks": steps1 - steps0, "num_slots": self.sys["num_slots"],
            "tokens_out": sum(r["emitted"] for r in rows),
            "server_stats": {k: stats[k] for k in
                             ("completed", "failed", "decode_steps", "rejected")},
            "n_params": self.n_params,
            "longest_silence_s": silence, "longest_silence_at_s": silence_at,
        }

    # -- after the window -----------------------------------------------------
    def end_window(self):
        """Stop without serving the backlog.  The lanes still finish what is
        in flight (the program has no abort), which can take as long as the
        longest answer; the reference runs meanwhile."""
        self._stopper = threading.Thread(
            target=self.server.stop, kwargs={"drain": False}, daemon=True)
        self._stopper.start()

    def finish(self):
        self._stopper.join(timeout=300)
        if self._stopper.is_alive():
            raise RuntimeError("the server did not stop")
        for rep in self.server.replicas:
            if rep.prefill.error is not None or rep.decode.error is not None:
                raise RuntimeError(f"lane error: {rep.prefill.error!r} / "
                                   f"{rep.decode.error!r}")
        self.server = self.net = None
        gc.collect()

    def check(self, control):
        """Served tokens against the plain reference: see references/llama.py
        ``served_gaps``.  Returns rows (name, value, limit)."""
        done = [r for r in self._rows if r["finished"]]
        chk = self.mix["check"]
        out = []
        bad = 0
        for r in done:
            res = r["_rec"]["req"].future.result()
            p = r["_rec"]["item"]["prompt"]
            if res.shape != (len(p) + r["n_out"],) or not (res[:len(p)] == p).all():
                bad += 1
        out.append(("answers_malformed", float(bad), 0.0))
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
            res = done[i]["_rec"]["req"].future.result()
            n = done[i]["n_prompt"]
            prompts.append(res[:n])
            served.append(res[n:])
        max_rows = int(chk["requests"]) * int(self.mix["output_tokens"]["hi"])
        gaps = self.ref.served_gaps(self.cfg, self.seed, prompts, served,
                                    int(chk["pad_tokens"]), max_rows)
        self.checked_tokens = int(len(gaps))
        out.append(("served_logit_gap_max", float(gaps.max()), chk["gap_limit"]))
        if control:
            cg = self.ref.served_gaps(self.cfg, self.seed, prompts, served,
                                      int(chk["pad_tokens"]), max_rows,
                                      lowp_control=True)
            out.append(("control.served_logit_gap_max", float(cg.max()), None))
            out.append(("control.served_logit_gap_mean", float(cg.mean()), None))
            out.append(("served_logit_gap_mean", float(gaps.mean()), None))
        return out

"""Plain reference of the SDAR-MoE decoder (``model_type`` ``sdar_moe``: the
Qwen3-MoE layer, generating by diffusion over blocks): float32 ``jax.numpy``
under ``jax.default_matmul_precision("highest")``; no kernels, no cache, no
batching, and nothing of the program is imported.

The equations, with ``h`` the residual stream and ``norm`` an RMSNorm with a
learned weight (``rms_norm_eps``), no biases anywhere:

* layer: ``h = h + attention(norm_attn(h))``, ``h = h + experts(norm_ffn(h))``,
  every layer alike (``mlp_only_layers`` empty, ``decoder_sparse_step`` 1);
* attention: grouped-query; ``q`` and ``k`` pass an RMSNorm over a head's
  channels (one weight of ``head_dim``, shared by the heads) before the rotary
  embedding over the whole head; softmax under an EXPLICIT boolean mask; output
  projection.  Generation's mask: position ``p`` sees key ``t`` iff
  ``t < (p // B + 1) * B`` (``block_mask``): whole earlier blocks and its own
  block in both directions;
* experts: ``s = softmax(W_g u)`` in float32 over all experts; the chosen are
  the ``num_experts_per_tok`` highest; their weights are ``s`` there over their
  sum (``norm_topk_prob``); the output is the weighted sum of the chosen
  experts' SwiGLUs.  No shared expert, no capacity: the reference computes every
  expert on every row and weights by a matrix that is zero where an expert was
  not chosen, so nothing can be dropped;
* model: embedding, the layers, a final RMSNorm, an untied head.  Row ``p``'s
  logits are the distribution of token ``p`` itself (NOT shifted); a position
  not yet decided holds ``mask_token_id``.

Generation (``generate``; the family's published block-diffusion sampler,
``low_confidence_dynamic``, greedy): the prompt's whole blocks are context; the
rest of the prompt opens the first decoded block beside masks.  A block:
repeat: if no position is masked the block is done (the published loop runs it
once more to store its keys and values; without a cache that pass has nothing
to do); else one pass, ``x0 = argmax`` and ``c = max softmax`` a row, and among
the masked rows commit those with ``c > threshold`` if they are at least
``n[step]``, else the ``n[step]`` of highest ``c``.

Departures from the publication, each a choice that seeded weights cannot tell
apart or that the configuration's ``assumed`` states:

* the rotation pairs (2i, 2i+1) as ``references/llama.py`` does;
* the program's router (``moe.route``) divides the chosen scores by their sum
  PLUS 1e-6; this file divides by the sum alone, as published: a relative
  difference of at most 1e-6 / sum, sum >= 8/128, far below bfloat16's
  rounding, and it is inside what the limits allow;
* among equal confidences the earlier position is committed first
  (``torch.topk``'s order there is unspecified);
* ``n[step]`` rows are committed among the MASKED rows only; the published
  loop's ``topk`` over a row of ``-inf`` could pick a decided position when
  fewer than ``n[step]`` are masked, which cannot happen at ``block_length`` =
  ``denoising_steps`` (one a step).

The weights are made here from the seed, a layer at a time and the experts a
group at a time; the benchmark hands the same values to the program, never the
other way round.  An expert's matrices come from a key of its own.  ``lowp``
rounds every matrix product's operands (the router's too) to float8: the
control, the step below the bfloat16 the configuration states.

The shared arithmetic (float8 rounding, RMSNorm, the seeded experts and their
blockwise sum) is ``references/lfm2.py``'s and ``references/llama.py``'s own
code: the first is loaded here under a name of its own and brings the second.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

_spec = importlib.util.spec_from_file_location(
    "chipbench_reference_sdar_shared",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "lfm2.py"))
_moe = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_moe)
_base = _moe._base

_fp8, _mm, _rms = _base._fp8, _base._mm, _base._rms
_normal, init_experts, experts_part = (_moe._normal, _moe.init_experts,
                                       _moe.experts_part)
layer_key, top_key = _base.layer_key, _base.top_key
EXPERT_GROUP = 8      # experts made and computed at a time
Q_BLOCK = 256         # attention is computed in blocks of this many query rows


def layer_shapes(cfg):
    """Leaf name -> shape, without the expert bank; matrices are (out, in)."""
    h, hd = cfg["hidden_size"], cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return {"q": (nq * hd, h), "k": (nkv * hd, h), "v": (nkv * hd, h),
            "o": (h, nq * hd), "router": (cfg["num_experts"], h)}


def init_layer(key, cfg, dtype, experts=True):
    """One layer's weights from its key; ``experts=False`` leaves the expert
    bank out (``block_rows`` makes it a group at a time)."""
    shapes = layer_shapes(cfg)
    keys = jax.random.split(key, len(shapes))
    w = {n: _normal(k, shapes[n], dtype, cfg)
         for k, n in zip(keys, sorted(shapes))}
    ones = jnp.ones((cfg["hidden_size"],), dtype)
    w.update(attn_norm=ones, ffn_norm=ones,
             q_norm=jnp.ones((cfg["head_dim"],), dtype),
             k_norm=jnp.ones((cfg["head_dim"],), dtype))
    if experts:
        w.update(init_experts(key, cfg, dtype, 0, cfg["num_experts"]))
    return w


def init_top(key, cfg, dtype):
    ke, kh = jax.random.split(key)
    v, h = cfg["vocab_size"], cfg["hidden_size"]
    return {"emb": _normal(ke, (v, h), dtype, cfg),
            "head": _normal(kh, (v, h), dtype, cfg),
            "norm": jnp.ones((h,), dtype)}


def block_mask(t, block):
    """(T, T) bool: position p sees key t iff t < (p // block + 1) * block."""
    p = np.arange(t)
    return p[None, :] < (p[:, None] // block + 1) * block


def _rope_at(x, positions, theta):
    """x (T, H, D) at the given positions: rotate pairs (2i, 2i+1) by
    position * theta^(-2i/D)."""
    d = x.shape[-1]
    inv = jnp.asarray(1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d)),
                      jnp.float32)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., ::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


def attention(u, w, cfg, positions, mask, lowp):
    """u (T, hidden), positions (T,), mask (T, T) bool -> (T, hidden)."""
    hd, theta, eps = cfg["head_dim"], cfg["rope_theta"], cfg["rms_norm_eps"]
    t = u.shape[0]
    q = _rms(_mm(u, w["q"], lowp).reshape(t, -1, hd), w["q_norm"], eps)
    k = _rms(_mm(u, w["k"], lowp).reshape(t, -1, hd), w["k_norm"], eps)
    v = _mm(u, w["v"], lowp).reshape(t, -1, hd)
    q, k = _rope_at(q, positions, theta), _rope_at(k, positions, theta)
    rep = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    if lowp:
        q, k, v = _fp8(q), _fp8(k), _fp8(v)
    blk = max(b for b in range(1, min(Q_BLOCK, t) + 1) if t % b == 0)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * blk, blk, axis=0)
        mb = jax.lax.dynamic_slice_in_dim(mask, i * blk, blk, axis=0)
        s = jnp.einsum("qhd,thd->hqt", qb, k) / np.sqrt(hd)
        p = jax.nn.softmax(jnp.where(mb[None], s, -jnp.inf), axis=-1)
        if lowp:
            p = _fp8(p)
        return jnp.einsum("hqt,thd->qhd", p, v)

    ctx = jax.lax.map(block, jnp.arange(t // blk)).reshape(t, -1)
    return _mm(ctx, w["o"], lowp)


def combine_weights(u, w, cfg, lowp):
    """-> ((T, experts) float32: an expert's weight for a row, zero where it
    was not among the row's chosen; (T,) the row's choice margin: by how much
    the last expert chosen leads the first one left out, in router logits,
    where rounding moves the choice)."""
    k = cfg["num_experts_per_tok"]
    logits = _mm(u, w["router"], lowp)
    s = jax.nn.softmax(logits, axis=-1)
    lead, idx = jax.lax.top_k(logits, k + 1)
    margin, idx = lead[:, k - 1] - lead[:, k], idx[:, :k]
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        chosen = chosen / chosen.sum(-1, keepdims=True)
    rows = jnp.arange(u.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, idx].add(chosen), margin


def layer_front(x, w, cfg, positions, mask, lowp=False):
    """Attention with its residual, then what the experts need: (x, the normed
    rows, the combine weights, the choice margins).  x (T, hidden) float32."""
    w = {n: a.astype(jnp.float32) for n, a in w.items()}
    eps = cfg["rms_norm_eps"]
    x = x + attention(_rms(x, w["attn_norm"], eps), w, cfg, positions, mask,
                      lowp)
    h = _rms(x, w["ffn_norm"], eps)
    return (x, h) + combine_weights(h, w, cfg, lowp)


def layer_forward(x, w, cfg, positions, mask, lowp=False):
    """One whole layer over one sequence from a full set of weights (the
    tests' sizes; ``block_rows`` makes the experts in groups instead)."""
    x, h, comb, _margin = layer_front(
        x, {n: a for n, a in w.items() if not n.startswith("w_")}, cfg,
        positions, mask, lowp)
    return x + experts_part(h, comb, {n: w[n] for n in
                                      ("w_gate", "w_up", "w_down")}, lowp)


@functools.partial(jax.jit, static_argnums=(0, 5))
def _forward(cfg_json, weights, ids, positions, mask, lowp):
    cfg = json.loads(cfg_json)
    with jax.default_matmul_precision("highest"):
        x = weights["top"]["emb"].astype(jnp.float32)[ids]
        for w in weights["layers"]:
            x = layer_forward(x, w, cfg, positions, mask, lowp)
        h = _rms(x, weights["top"]["norm"].astype(jnp.float32),
                 cfg["rms_norm_eps"])
        return _mm(h, weights["top"]["head"].astype(jnp.float32), lowp)


def forward(cfg, weights, ids, positions=None, mask=None, lowp=False):
    """Logits (T, vocab) of one sequence from given weights ``{"top": ...,
    "layers": [...]}``: row p for token p.  ``positions`` default to 0..T-1,
    ``mask`` to the block mask of ``cfg["block_length"]``."""
    t = len(ids)
    positions = np.arange(t) if positions is None else positions
    mask = block_mask(t, cfg["block_length"]) if mask is None else mask
    return _forward(_cfg_json(cfg), weights, jnp.asarray(ids, jnp.int32),
                    jnp.asarray(positions, jnp.int32), jnp.asarray(mask),
                    bool(lowp))


def transfer_schedule(cfg):
    """Commits a denoising pass at least: block_length over the passes, the
    remainder on the first."""
    base, rem = divmod(cfg["block_length"], cfg["denoising_steps"])
    return [base + (i < rem) for i in range(cfg["denoising_steps"])]


def choose(conf, masked, step, cfg):
    """Which of a block's rows a pass commits, from each row's confidence
    (B,) and which rows are masked: those above the threshold if they are at
    least ``n[step]``, else the ``n[step]`` most confident (the earlier
    position among equals)."""
    conf = np.where(masked, conf, -np.inf)
    need = transfer_schedule(cfg)[step]
    commit = conf > cfg["confidence_threshold"]
    if commit.sum() < need:
        commit = np.zeros(len(masked), bool)
        commit[sorted(np.flatnonzero(masked),
                      key=lambda j: (-conf[j], j))[:need]] = True
    return commit


def commit_rule(logits, masked, step, cfg):
    """One pass's logits (B, vocab) and which rows are masked -> (x0 (B,),
    which rows the pass commits (B,) bool)."""
    logits = np.asarray(logits, np.float32)
    e = np.exp(logits - logits.max(-1, keepdims=True))
    return logits.argmax(-1), choose((e / e.sum(-1, keepdims=True)).max(-1),
                                     masked, step, cfg)


def generate(cfg, weights, prompt, max_new_tokens, lowp=False):
    """The plain generation loop, the whole sequence recomputed every pass ->
    (the tokens at the first ``max_new_tokens`` positions behind the prompt,
    every commit ``(position, token, the block's pass)`` in the order made).
    It stops with the pass that commits the last of those tokens."""
    bl, mask_id = cfg["block_length"], cfg["mask_token_id"]
    prompt = np.asarray(prompt, np.int32)
    p = len(prompt)
    end = p + max_new_tokens
    # every pass runs the sequence at one length (a compile a length): the
    # blocks to come hold mask ids, which no earlier row sees; the length is
    # rounded up so that requests share it
    total = -(-end // (8 * bl)) * 8 * bl
    x = np.full(total, mask_id, np.int32)
    x[:p] = prompt
    undecided = np.arange(total) >= p
    commits = []
    for b0 in range(p // bl * bl, total, bl):
        for step in range(cfg["denoising_steps"] + 1):
            masked = undecided[b0:b0 + bl]
            if not masked.any():
                break       # the pass that stores the block: nothing to keep
            logits = np.asarray(forward(cfg, weights, x,
                                        lowp=lowp))[b0:b0 + bl]
            x0, commit = commit_rule(logits, masked, step, cfg)
            for j in np.flatnonzero(commit):
                x[b0 + j] = x0[j]
                undecided[b0 + j] = False
                commits.append((b0 + int(j), int(x0[j]), step))
            if not undecided[p:end].any():
                return x[p:end].copy(), commits
    raise AssertionError("the schedule commits every position")


# -- the benchmark's comparison: what every pass saw, rebuilt -----------------------

def rebuild(prompt, commits, block, mask_id, pad_to):
    """One request's passes as rows of one masked forward.

    ``commits``: the server's record ``(position, token, the block's pass)`` of
    every commit.  The sequence is the CONTEXT (prompt and final tokens of
    every block before the last one decoded, under the block mask: the keys and
    values the cache holds for later blocks) followed by one group of ``block``
    rows for every pass that committed something: the block as that pass saw
    it (what earlier passes committed, mask ids elsewhere), at the block's own
    positions, seeing the context before its block and its own group.
    -> (ids, positions (pad_to,), mask (pad_to, pad_to), the served token a row
    (pad_to,; 0 where none), the rows of the commits in the order given,
    groups ``[(first row, pass, masked (block,), committed (block,))]``)."""
    p = len(prompt)
    final = {pos: (tok, step) for pos, tok, step in commits}
    last = max(final) // block * block
    ids = [int(prompt[pos]) if pos < p else final[pos][0]
           for pos in range(last)]
    positions = list(range(last))
    toks = [0] * last
    groups, at = [], {}
    for b0 in range(p // block * block, last + block, block):
        span = range(b0, b0 + block)
        for k in sorted({final[pos][1] for pos in span if pos in final}):
            start = len(ids)
            decided = [pos < p or (pos in final and final[pos][1] < k)
                       for pos in span]
            now = [pos in final and final[pos][1] == k for pos in span]
            for pos, known, mine in zip(span, decided, now):
                ids.append(mask_id if not known else
                           int(prompt[pos]) if pos < p else final[pos][0])
                toks.append(final[pos][0] if mine else 0)
                positions.append(pos)
                if mine:
                    at[pos] = len(ids) - 1
            groups.append((start, k, ~np.asarray(decided), np.asarray(now)))
    n = len(ids)
    assert n <= pad_to, (n, pad_to)
    mask = np.zeros((pad_to, pad_to), bool)
    mask[:last, :last] = block_mask(last, block)
    for start, _k, _m, _c in groups:
        b0 = positions[start]
        mask[start:start + block, :b0] = True
        mask[start:start + block, start:start + block] = True
    pad = np.arange(n, pad_to)
    mask[pad, pad] = True           # a padded row sees itself, and is not read
    fill = [0] * (pad_to - n)
    return (np.asarray(ids + fill, np.int32),
            np.asarray(positions + fill, np.int32), mask,
            np.asarray(toks + fill, np.int32),
            [at[pos] for pos, _tok, _step in commits], groups)


ROW_CHUNK = 1024      # rows whose logits are on the device at a time


@functools.lru_cache(maxsize=None)
def _programs(cfg_json, dtype_name, lowp):
    cfg = json.loads(cfg_json)
    dtype = jnp.dtype(dtype_name)

    @jax.jit
    def embed(seed_key, ids):
        top = init_top(top_key(seed_key), cfg, dtype)
        return top["emb"].astype(jnp.float32)[ids]

    @jax.jit
    def front(seed_key, l, xs, positions, masks):
        w = init_layer(layer_key(seed_key, l), cfg, dtype, experts=False)
        with jax.default_matmul_precision("highest"):
            return jax.lax.map(
                lambda a: layer_front(a[0], w, cfg, a[1], a[2], lowp),
                (xs, positions, masks))

    @functools.partial(jax.jit, donate_argnums=5)
    def group(seed_key, l, first, hs, combs, acc):
        bank = init_experts(layer_key(seed_key, l), cfg, dtype, first,
                            EXPERT_GROUP)
        n, t, h = hs.shape
        comb = jax.lax.dynamic_slice_in_dim(combs.reshape(n * t, -1), first,
                                            EXPERT_GROUP, axis=1)
        with jax.default_matmul_precision("highest"):
            return acc + experts_part(hs.reshape(n * t, h), comb, bank,
                                      lowp).reshape(n, t, h)

    @jax.jit
    def read(seed_key, xs, toks):
        """Of every row's logits, a chunk of rows at a time: the best, which
        token holds it, the logit of the token given, the standard deviation
        and the log of the sum of exponentials."""
        top = init_top(top_key(seed_key), cfg, dtype)
        norm = top["norm"].astype(jnp.float32)
        head = top["head"].astype(jnp.float32)
        n, t, h = xs.shape
        chunk = max(c for c in range(1, min(ROW_CHUNK, n * t) + 1)
                    if (n * t) % c == 0)

        def one(a):
            x, tok = a
            with jax.default_matmul_precision("highest"):
                lg = _mm(_rms(x, norm, cfg["rms_norm_eps"]), head, lowp)
            return (lg.max(-1), lg.argmax(-1),
                    jnp.take_along_axis(lg, tok[:, None], axis=-1)[:, 0],
                    lg.std(-1), jax.nn.logsumexp(lg, axis=-1))

        out = jax.lax.map(one, (xs.reshape(-1, chunk, h),
                                toks.reshape(-1, chunk)))
        return tuple(a.reshape(n, t) for a in out)

    return embed, front, group, read


def _cfg_json(cfg):
    keep = ("hidden_size", "moe_intermediate_size", "head_dim",
            "num_attention_heads", "num_key_value_heads", "vocab_size",
            "rope_theta", "rms_norm_eps", "num_hidden_layers", "num_experts",
            "num_experts_per_tok", "norm_topk_prob", "initializer_range",
            "block_length")
    return json.dumps({k: cfg[k] for k in keep}, sort_keys=True)


def block_rows(cfg, seed, ids, positions, masks, toks, lowp=False):
    """The masked forward, weights remade from the seed: ``ids``, ``positions``
    and ``toks`` (N, T), ``masks`` (N, T, T) bool.  A layer's weights, and of
    its experts a group's, on the device at a time.  -> of every row, each
    (N, T) on the host: the best logit, its token, the logit of ``toks``, the
    logits' standard deviation, their log-sum-exp, and the smallest choice
    margin over the layers."""
    embed, front, group, read = _programs(_cfg_json(cfg), cfg["torch_dtype"],
                                          bool(lowp))
    key = jax.random.PRNGKey(seed % (2 ** 31 - 1))
    xs = embed(key, jnp.asarray(ids, jnp.int32))
    positions, masks = jnp.asarray(positions, jnp.int32), jnp.asarray(masks)
    assert cfg["num_experts"] % EXPERT_GROUP == 0
    margins = jnp.full(xs.shape[:2], jnp.inf, jnp.float32)
    for l in range(cfg["num_hidden_layers"]):
        xs, hs, combs, margin = front(key, jnp.int32(l), xs, positions, masks)
        margins = jnp.minimum(margins, margin)
        acc = jnp.zeros_like(xs)
        for first in range(0, cfg["num_experts"], EXPERT_GROUP):
            acc = group(key, jnp.int32(l), jnp.int32(first), hs, combs, acc)
        xs = xs + acc
    out = read(key, xs, jnp.asarray(toks, jnp.int32))
    return tuple(np.asarray(a) for a in out + (margins,))


def commit_regret(lc, masked, committed, need, log_threshold):
    """How far a pass's commits lie from the commit rule, by the reference's
    own log-confidences ``lc`` (B,) of the block as that pass saw it: 0 where
    ``choose`` on ``lc`` commits exactly the rows ``committed``; else the
    widest of: a masked row left behind that is more confident than a
    committed one (by how much); a row left behind though it, and every
    committed one, is past the threshold; a commit beyond the pass's share
    ``need`` that is not past the threshold.  In log-confidence, which moves as
    the logits do."""
    inn, out = lc[committed], lc[masked & ~committed]
    r = 0.0
    if len(out):
        r = max(r, out.max() - inn.min())
        if len(inn) >= need and inn.min() > log_threshold:
            r = max(r, out.max() - log_threshold)
    if len(inn) > need:
        r = max(r, log_threshold - inn.min())
    return float(r)


def served_gaps(cfg, seed, prompts, commits, pad_to, lowp_control=False):
    """The serving comparison.  Each request's passes are rebuilt from the
    server's commit record (``rebuild``) and run once through the reference; at
    each committed token, in the pass that committed it, the gap is (the
    reference's best logit at that row) - (its logit of the served token), in
    units of the row's logit standard deviation.  With ``lowp_control`` the
    float8 reference takes the program's place: the gap is read for the token
    it puts first at the same rows, and the rows its own confidences commit
    take the place of the served commits.  -> (gaps, the float32 pass's choice
    margin at each of those rows, the share of passes in which the reference's
    own confidences would have committed other positions than were, and of
    every pass that had a choice (a masked row it left behind, or more commits
    than its share) its ``commit_regret`` in units of the block's mean logit
    standard deviation, (passes, 3): of the commits made, and, as readings of
    what a wrong rule on the device would come to, of the pass's share taken
    from the LEAST confident rows and from the masked rows in position
    order)."""
    bl, mask_id = cfg["block_length"], cfg["mask_token_id"]
    built = [rebuild(p, c, bl, mask_id, pad_to) for p, c in zip(prompts, commits)]
    ids, positions, masks, toks = (np.stack([b[i] for b in built])
                                   for i in range(4))
    if lowp_control:
        low = block_rows(cfg, seed, ids, positions, masks, toks, lowp=True)
        toks, low_conf = low[1], np.exp(low[0] - low[4])
    best, _top, got, std, lse, margin = block_rows(cfg, seed, ids, positions,
                                                   masks, toks)
    schedule = transfer_schedule(cfg)
    log_thr = float(np.log(cfg["confidence_threshold"]))
    gaps, margins, regrets, other, passes = [], [], [], 0, 0
    for i, b in enumerate(built):
        at = np.asarray(b[4])
        gaps.append((best[i, at] - got[i, at]) / std[i, at])
        margins.append(margin[i, at])
        for start, k, masked, committed in b[5]:
            rows = slice(start, start + bl)
            lc = best[i, rows] - lse[i, rows]
            if lowp_control:
                committed = choose(low_conf[i, rows], masked, k, cfg)
            other += int((choose(np.exp(lc), masked, k, cfg) != committed).any())
            passes += 1
            need = schedule[k]
            if masked.sum() == committed.sum() <= need:
                continue                    # no choice: every masked row went
            by_conf = sorted(np.flatnonzero(masked), key=lambda j: (lc[j], j))
            wrong = [np.isin(np.arange(bl), rows_[:need]) for rows_ in
                     (by_conf, np.flatnonzero(masked))]
            regrets.append([commit_regret(lc, masked, c, need, log_thr)
                            / std[i, rows].mean()
                            for c in [committed] + wrong])
    return (np.concatenate(gaps), np.concatenate(margins), other / passes,
            np.asarray(regrets, np.float64).reshape(-1, 3))

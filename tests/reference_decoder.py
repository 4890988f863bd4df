"""Plain float32 reference: the text decoder of Kimi-VL-A3B (the DeepSeek-V3
block: latent attention, sigmoid-routed experts beside shared ones), forward,
loss and, through ``jax.grad`` of ``objective``, gradients.

``jax.numpy`` only: no flax, no kernel, no sort, no cache, nothing from the
program but the names in its parameter tree (``models/decoder.py``).
Attention is explicit scores and a softmax; the expert layer is a masked loop
over experts, each applied to every token.  ``cfg`` is the configuration file
(the catalog's key names).  Run it under
``jax.default_matmul_precision("highest")``.

The same file is the uncut model and one chip's share of it:

- ``experts_held=(first, count)``: router, top-k, normalisation and scaling
  run over all ``n_routed`` experts; the sum runs over the held ones only,
  and the shared expert is computed in full.  ``None`` holds all of them.
  An expert stack with more than ``count`` entries is sliced to the share.
- ``vocab``: the first ``vocab`` rows of the embedding and of the head; a
  sliced vocabulary is a smaller vocabulary.  ``None`` takes the tree's.
- ``q_block``: attention computed for that many query rows at a time, each
  block recomputed in the backward pass, so that L = 8192 fits beside a
  train state.  The numbers do not change; ``None`` is one block.

Departures from the published model, each also under ``assumed`` or
``reduced`` in ``benchmark/configs/kimi-vl-a3b-ep8.json``:

- the vision tower (MoonViT) and its projector are absent: the source's
  language-model settings carry no tower sizes, so this is the text decoder
  on token batches;
- rotary pairs are half-split (dimension i turns with i + 32), not
  interleaved: a convention of the checkpoint's layout, invisible with
  seeded weights, and the program's;
- the selection bias's update rate (0.001) and the sequence-wise balance
  loss's weight (1e-4) are not in the config: DeepSeek-V3's values.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

# What `correct` allows between the program under its bf16 policy and this
# reference, on the chip, at the published widths and L = 8192 (one sequence
# of the resident batch, the weights the run starts from, the selection bias
# drawn non-zero); ``agreement`` computes the measures.
#
# top-6 of 64 is discontinuous: a position at which a held expert's biased
# score lies closer to being picked or dropped than the rounding of the
# router's bf16 input is routed otherwise by the program than by float32,
# which is another valid routing and moves that position's logits by tens
# of percent of the largest (``logits_all_max`` reads 0.23-0.31 under bf16).
# Such positions are taken out of the measures and nothing else is: the
# reference's own margins say which they are (``clear_of_ties``), logits
# are compared at the others, and both losses, and so the gradients, are
# means over the others.  TIE_GAP lies past where the flips end, on the v5e
# (PR 26; PERF.md): the largest error at the clear positions reads
# 0.19-0.25 of the largest logit at a gap of 0.004, 0.018-0.108 at 0.008,
# 0.014-0.028 on twelve seeds and 0.082 (one flip) on a thirteenth at
# 0.012, and 0.016-0.029 on thirteen seeds at 0.016, 0.02 and 0.024 alike.
# At 0.02, 71-87% of the positions are at a tie in one of the five layers
# and 1,000-2,400 of 8,192 are compared.
#
# Each limit lies between two readings there: the largest the program gave
# over seven seeds at 0.02, and the smallest the program on 8-bit (e4m3)
# weights gave on three seeds at gaps of 0.016 and 0.024.
# - logits_max: a position's largest logit error over the largest logit,
#   the maximum over the clear positions.  0.0165-0.0294; 8-bit 0.26-0.36.
#   Six blocks of bf16 products with float32 norms, router, softmax and
#   accumulation come to 1% of the largest logit at the median position
#   (``logits_all_p50`` 0.010-0.012) and two to three times that at the
#   worst.
# - loss_abs: the mean over a thousand targets or two averages rounding
#   out.  0.0000-0.0015 (0.0020 at smaller gaps); 8-bit 0.0115-0.0266 on
#   two seeds and 0.0002-0.0071 on the third, which every other limit
#   catches.
# - grad_rel/<leaf>, each of GRAD_LEAVES: |g - g_ref| / |g_ref| (Frobenius)
#   over the whole leaf.  The first layer's W_kvb 0.0130-0.0158 (8-bit
#   0.204-0.233) and W_q 0.015-0.023 (0.26-0.33); the last layer's held
#   output projections 0.015-0.019 (0.32-0.60) and router 0.028-0.097
#   (0.45-1.10; over every gap tried, 0.002 to 0.024, and 25 seeds it read
#   up to 0.19: a router's gradient is a small difference of the experts'
#   outputs, so rounding weighs more on it).
# - tied_share: no reading of the precision (both sides read the same
#   0.71-0.87) but a guard: a mask that left nothing to compare would pass
#   everything.
TIE_GAP = 0.02
TOLERANCE = {
    "logits_max": 0.08, "loss_abs": 0.008, "tied_share": 0.95,
    "grad_rel/layer_0/attn/kv_b_proj/kernel": 0.05,
    "grad_rel/layer_0/attn/q_proj/kernel": 0.08,
    "grad_rel/layer_last/moe/experts/down_proj": 0.1,
    "grad_rel/layer_last/moe/router/kernel": 0.35}

# The leaves whose gradients the chip comparison reads (the whole tree in
# float32 would not fit beside the train state): router and the held experts'
# output projection of the last layer, W_kvb and W_q of the first.
GRAD_LEAVES = (("layer_last", "moe", "router", "kernel"),
               ("layer_last", "moe", "experts", "down_proj"),
               ("layer_0", "attn", "kv_b_proj", "kernel"),
               ("layer_0", "attn", "q_proj", "kernel"))

BIAS_UPDATE_RATE = 0.001   # assumed: DeepSeek-V3's gamma
SEQ_AUX_ALPHA = 1e-4       # assumed: DeepSeek-V3's alpha

_HI = lax.Precision.HIGHEST


def _mm(a, b):
    return jnp.matmul(a, b, precision=_HI)


def rms_norm(x, scale, eps):
    return scale * x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def swiglu(x, p):
    gate = _mm(x, p["gate_proj"]["kernel"])
    up = _mm(x, p["up_proj"]["kernel"])
    return _mm(gate / (1.0 + jnp.exp(-gate)) * up, p["down_proj"]["kernel"])


def rope(x, theta):
    """Half-split rotary embedding over the last axis of [B, L, H, D]."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def mla(cfg, p, x, q_block=None):
    """Latent attention; the query is not compressed (``q_lora_rank`` null)."""
    b, l, _ = x.shape
    h = cfg["num_attention_heads"]
    nope, rot, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    rank, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    q = _mm(x, p["q_proj"]["kernel"]).reshape(b, l, h, nope + rot)
    ckv = _mm(x, p["kv_a_proj"]["kernel"])
    c, k_rope = ckv[..., :rank], ckv[..., rank:]
    kv = _mm(rms_norm(c, p["kv_a_norm"]["scale"], eps),
             p["kv_b_proj"]["kernel"]).reshape(b, l, h, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q_rope = rope(q[..., nope:], cfg["rope_theta"])
    k_rope = rope(k_rope[:, :, None, :], cfg["rope_theta"])
    q = jnp.concatenate([q[..., :nope], q_rope], -1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (b, l, h, rot))], -1)
    scale = 1.0 / jnp.sqrt(jnp.float32(nope + rot))
    kpos = jnp.arange(l)

    def rows(q_rows, first):
        s = jnp.einsum("bqhd,bkhd->bhqk", q_rows, k, precision=_HI) * scale
        qpos = first + jnp.arange(q_rows.shape[1])
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        s = s - s.max(-1, keepdims=True)
        w = jnp.exp(s)
        w = w / w.sum(-1, keepdims=True)
        return jnp.einsum("bhqk,bkhd->bqhd", w, v, precision=_HI)

    if q_block is None or q_block >= l:
        o = rows(q, 0)
    else:
        blocks = q.reshape(b, l // q_block, q_block, h, nope + rot)
        o = lax.map(
            lambda a: jax.checkpoint(rows)(a[0], a[1]),
            (jnp.moveaxis(blocks, 1, 0), jnp.arange(0, l, q_block)))
        o = jnp.moveaxis(o, 0, 1).reshape(b, l, h, vd)
    return _mm(o.reshape(b, l, h * vd), p["o_proj"]["kernel"])


def route(cfg, router_kernel, bias, x):
    """Scores [.., E], the chosen experts [.., K], their gates [.., K], and
    the K-th and (K+1)-th largest biased scores [.., 2]: the two between
    which the selection is decided."""
    k = cfg["num_experts_per_tok"]
    s = 1.0 / (1.0 + jnp.exp(-_mm(x, router_kernel)))
    top, idx = lax.top_k(s + bias, k + 1)    # the bias selects, no more
    idx = idx[..., :k]
    g = jnp.take_along_axis(s, idx, -1)
    if cfg["norm_topk_prob"]:
        g = g / (g.sum(-1, keepdims=True) + 1e-20)
    return s, idx, g * cfg["routed_scaling_factor"], top[..., k - 1:]


def balance_loss(s, idx, k, alpha=SEQ_AUX_ALPHA):
    """Sequence-wise balance loss, averaged over the batch's sequences:
    alpha * sum_i f_i P_i with f_i = E / (K L) * (tokens of the sequence
    that chose i), P_i = mean_t s_ti / sum_j s_tj.  s: [B, L, E]."""
    e, l = s.shape[-1], s.shape[1]
    chose = (idx[..., None] == jnp.arange(e)).any(-2).astype(jnp.float32)
    f = chose.sum(1) * (e / (k * l))
    p = (s / s.sum(-1, keepdims=True)).mean(1)
    return alpha * jnp.mean(jnp.sum(f * p, -1))


def expert_layer(cfg, p, bias, x, experts_held=None):
    """The expert layer's output, its balance loss, its counts [E], and
    each token's margin: how far the biased score of the nearest held
    expert lies from being picked or dropped (for a chosen one, above the
    (K+1)-th largest; for another, below the K-th).  A token whose margin
    is smaller than the rounding of the router's input is at a tie: a
    bf16 program may route it otherwise than float32 does, and both are
    right.

    The router's width is the published count of experts; the experts of
    ``p`` are the held ones, or a larger stack that is sliced."""
    e_all = p["router"]["kernel"].shape[-1]
    first, count = experts_held or (0, e_all)
    s, idx, g, top = route(cfg, p["router"]["kernel"], bias, x)
    kth, nxt = top[..., :1], top[..., 1:]
    mine = (s + bias)[..., first:first + count]
    margin = jnp.where(mine >= kth, mine - nxt, kth - mine).min(-1)
    stack = p["experts"]
    offset = first if stack["gate_proj"].shape[0] > count else 0
    y = swiglu(x, p["shared"])
    for j in range(count):
        e = first + j
        gate = jnp.sum(jnp.where(idx == e, g, 0.0), -1, keepdims=True)
        one = {name: {"kernel": stack[name][offset + j]}
               for name in ("gate_proj", "up_proj", "down_proj")}
        y = y + gate * swiglu(x, one)
    counts = (idx[..., None] == jnp.arange(e_all)).sum((0, 1, 2))
    return (y, balance_loss(s, idx, cfg["num_experts_per_tok"]), counts,
            margin)


def forward(cfg, params, bias, tokens, experts_held=None, vocab=None,
            q_block=None):
    """Logits [B, L, V], the summed balance loss, the counts by layer, and
    each position's smallest ``expert_layer`` margin over the layers [B, L].

    ``bias``: {"layer_i": [E]} for the expert layers.  Each block is
    recomputed in the backward pass (``jax.checkpoint``): memory, not
    numbers."""
    eps = cfg["rms_norm_eps"]
    embedding, head = params["embed"]["embedding"], params["head"]["weight"]
    if vocab is not None:
        embedding, head = embedding[:vocab], head[:vocab]
    x = embedding[tokens]
    aux, counts = jnp.float32(0.0), {}
    margin = jnp.full(tokens.shape, jnp.inf)
    for i in range(cfg["num_hidden_layers"]):
        p = params[f"layer_{i}"]

        def block(x, p, b):
            h = x + mla(cfg, p["attn"],
                        rms_norm(x, p["attn_norm"]["scale"], eps), q_block)
            z = rms_norm(h, p["ffn_norm"]["scale"], eps)
            if "moe" in p:
                y, a, c, m = expert_layer(cfg, p["moe"], b, z, experts_held)
                return h + y, a, c, m
            return h + swiglu(z, p["mlp"]), jnp.float32(0.0), None, None

        x, a, c, m = jax.checkpoint(block)(x, p, bias.get(f"layer_{i}"))
        aux = aux + a
        if c is not None:
            counts[f"layer_{i}"] = c
            margin = jnp.minimum(margin, m)
    x = rms_norm(x, params["norm_f"]["scale"], eps)
    return (jnp.einsum("bld,vd->blv", x, head, precision=_HI), aux, counts,
            lax.stop_gradient(margin))


def loss(logits, tokens, weight=None):
    """Mean next-token cross-entropy: position t predicts token t + 1.
    ``weight`` [B, L]: the mean is over the positions it marks."""
    logits, targets = logits[:, :-1], tokens[:, 1:]
    m = logits.max(-1, keepdims=True)
    logp = logits - m - jnp.log(jnp.sum(jnp.exp(logits - m), -1,
                                        keepdims=True))
    nll = -jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]
    if weight is None:
        return jnp.mean(nll)
    weight = weight[:, :-1].astype(nll.dtype)
    return jnp.sum(nll * weight) / jnp.sum(weight)


def objective(cfg, params, bias, tokens, **kw):
    """What a step minimises: cross-entropy plus the balance loss."""
    logits, aux, _, _ = forward(cfg, params, bias, tokens, **kw)
    return loss(logits, tokens) + aux


def bias_update(bias, counts, rate=BIAS_UPDATE_RATE):
    """noaux_tc: b_i <- b_i + rate * sign(mean_j c_j - c_i), one layer."""
    counts = counts.astype(jnp.float32)
    return bias + rate * jnp.sign(counts.mean() - counts)


def grad_leaves(tree, n_layers):
    """``GRAD_LEAVES`` out of a tree shaped like the params."""
    out = {}
    for path in GRAD_LEAVES:
        leaf = tree
        for key in path:
            leaf = leaf[f"layer_{n_layers - 1}" if key == "layer_last"
                        else key]
        out["/".join(path)] = leaf
    return out


def _rel(got, want):
    """|got - want| / |want| (Frobenius)."""
    return jnp.sqrt(jnp.sum((got - want) ** 2)) / jnp.sqrt(jnp.sum(want ** 2))


def clear_of_ties(margin):
    """The positions [B, L] whose routing rounding cannot move: every held
    expert's biased score at least ``TIE_GAP`` from being picked or
    dropped, in every expert layer."""
    return margin >= TIE_GAP


def agreement(logits, want_logits, loss_value, want_loss, grads, want_grads,
              clear):
    """The measures ``TOLERANCE`` limits, as arrays.  ``clear`` [B, L] is
    ``clear_of_ties`` of the reference's margins; both losses are means
    over those positions (``loss(..., weight=clear)``), and ``grads`` and
    ``want_grads`` are ``grad_leaves`` of the objectives with those
    losses.  ``logits_all_max`` and ``logits_all_p50``, over every
    position, are printed and not limited."""
    worst = jnp.max(jnp.abs(logits - want_logits), -1).reshape(-1)
    top = jnp.max(jnp.abs(want_logits))
    clear = clear.reshape(-1)
    out = {"logits_max": jnp.max(jnp.where(clear, worst, 0.0)) / top,
           "logits_all_max": jnp.max(worst) / top,
           "logits_all_p50": jnp.percentile(worst, 50) / top,
           "tied_share": 1.0 - jnp.mean(clear.astype(jnp.float32)),
           "loss_abs": jnp.abs(loss_value - want_loss)}
    for name, want in want_grads.items():
        out["grad_rel/" + name] = _rel(grads[name], want)
    return out


def within_tolerance(measures, slack: float = 1.0) -> bool:
    """Every limited measure within ``slack`` times its limit.  1 on the
    chip; the CPU tests' preset sums over a hundredth of the tokens and a
    thirtieth of the width, so that rounding averages out less, and they
    hold the bf16 policy inside twice the limits and 8-bit weights outside
    even those."""
    return all(float(measures[k]) <= slack * TOLERANCE[k] for k in TOLERANCE)

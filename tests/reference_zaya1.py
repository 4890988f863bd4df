"""Plain float32 reference: the text decoder of ZAYA1-8B (attention inside a
compressed latent with convolutions on queries and keys, grouped key-value
heads, an MLP router that picks one expert a token, a scaled residual, an
embedding tied to the head), forward, loss and, through ``jax.grad`` of
``objective``, gradients.

``jax.numpy`` only: no flax, no kernel, no sort, no cache, nothing from the
program but the names in its parameter tree (``models/decoder.py``).  The
convolutions are shifted sums; attention is explicit scores and a softmax,
K and V repeated to the query heads; the expert layer is a masked loop over
experts, each applied to every token.  ``cfg`` is the configuration file
(the catalog's key names).  Run it under
``jax.default_matmul_precision("highest")``.

The equations, with ``d`` the hidden size, ``H`` query heads over ``G``
key-value heads of ``hd`` channels, ``n = rms_norm(x)``:

- attention: ``q0 = W_q n`` [H, hd], ``k0 = W_k n`` [G, hd]; stacked as
  ``H + G`` heads ``z``; ``u_t = sum_j a[j] * z_{t-j} + a0`` (a tap a
  channel, ``cca_time0`` taps, zeros before the first position);
  ``w_t[h] = sum_j u_{t-j}[h] A[h, j] + b[h]`` (an ``hd x hd`` matrix a
  head and a tap, ``cca_time1`` taps); ``q = w_q + (q0 + rep(k0)) / 2``,
  ``k = w_k + (mean over the group's query heads of q0 + k0) / 2``; each
  head L2-normalised and multiplied by ``sqrt(hd)``, a key head by its
  group's temperature too; rotary on the first ``partial_rotary_factor``
  of a head; ``v_t = [W_v1 n_t ; W_v2 n_{t-1}]`` as G heads; causal softmax
  attention of query head ``h`` against key-value head ``h // (H / G)`` at
  scale ``hd ** -0.5``; ``o = W_o [heads]``.
- router: ``r = W_d n'``; from the second layer on ``r += gamma * r_before``
  (``r_before`` the layer before's ``r``, after its own addition);
  ``p = softmax(W_3 gelu(W_2 gelu(W_1 rms_norm(r) + b_1) + b_2))``, gelu
  exact; the pick is ``argmax(p + bias)``, the gate ``p[pick]``.
- block: ``x' = (a1 x + b1) + (a2 attention + b2)``,
  ``x'' = (a3 x' + b3) + (a4 gate * swiglu_pick(rms_norm(x')) + b4)``.
- ``logits = rms_norm(x) E^T`` with ``E`` the embedding.

The same file is the uncut model and one chip's share of it:

- ``experts_held=(first, count)``: the router scores all its experts; the
  sum runs over the held ones only (a token whose pick is absent adds
  nothing).  ``None`` holds all of them.  An expert stack with more than
  ``count`` entries is sliced to the share.
- ``vocab``: the first ``vocab`` rows of the embedding; a sliced vocabulary
  is a smaller vocabulary.  ``None`` takes the tree's.
- ``q_block``: attention computed for that many query rows at a time, each
  block recomputed in the backward pass; ``row_block``: the head and the
  loss that many rows at a time, so that L = 8192 over 131,136 ids fits
  beside a train state.  The numbers do not change; ``None`` is one block.

Departures from the published model, each also under ``assumed`` or
``reduced`` in ``benchmark/configs/zaya1-8b-ep2.json``: rotary pairs are
half-split inside the rotary part; the selection bias moves by this repo's
``noaux_tc`` rule, which stands in for the report's balancing controller;
the report's router output that skips the expert is left out; the
embedding's output is not scaled.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

# What `correct` allows between the program under its bf16 policy and this
# reference, on the chip, at the published widths and L = 8192 (one sequence
# of the resident batch, the weights the run starts from, the selection bias
# drawn and leaning: ``benchmark/runners/lm_top1_resident_step.py``);
# ``agreement`` computes the measures.
#
# One expert of 16 a token is discontinuous: a position at which the picked
# expert's biased probability lies closer to the runner-up's than the
# rounding of the router's input (the router itself is float32 on both
# sides; its input comes through bf16 products) may be routed otherwise by
# the program than by float32, which is another valid routing and moves that
# position's logits by 5-7% of the largest (``logits_all_max`` reads
# 0.052-0.073 under bf16).  Such positions are taken out of the measures and
# nothing else is: the reference's own margins say which they are
# (``clear_of_ties``), logits are compared at the others, and both losses,
# and so the gradients, are means over the others.  TIE_GAP lies past where
# the flips end, on the v5e (PR 32; PERF.md 6): of 8,192 positions 5 were
# routed otherwise on each of two seeds, at margins of 0.00003-0.00045, and
# the largest error at the clear positions reads 0.0077 and 0.0150 of the
# largest logit at a gap of 0.0005, 0.0077 and 0.0105 at 0.001, 0.002 and
# 0.004 alike; on two more seeds the 20 largest errors (0.041-0.058) sit at
# margins up to 0.00041.  At 0.002, 11-64% of the positions are at a tie in
# one of the four layers (a seeded router's two leading probabilities can
# lie that close for most tokens; at 0.004 it is twice that).
#
# Each limit lies between two readings there: the largest the program gave
# over fifteen seeds, and the smallest the program on 8-bit (e4m3) weights
# gave on four seeds (PERF.md 6, PR 32, has every reading).
# - logits_max: a position's largest logit error over the largest logit,
#   the maximum over the clear positions: four blocks of bf16 products with
#   float32 norms, router, softmaxes and accumulation.  0.0071-0.0144 on
#   thirteen seeds; 0.0197 and 0.0333 on two, each at the position after one
#   routed otherwise early in the sequence (positions 17 and 25), which
#   sees its neighbour through the taps, the value shift and an attention
#   over two dozen keys; 8-bit 0.0974-0.1080.
# - loss_abs: the mean over thousands of targets averages rounding out.
#   0.00002-0.0012; 8-bit 0.00001-0.0073, so it tells nothing of the
#   precision and takes the accepted LM cells' 0.008, seven times the
#   largest reading.
# - grad_rel/<leaf>, each of GRAD_LEAVES: |g - g_ref| / |g_ref| (Frobenius)
#   over the whole leaf.  The embedding 0.0047-0.0059 (8-bit 0.076-0.086),
#   the convolution's matrices 0.0090-0.0116 (0.134-0.154), the values'
#   shifted half 0.0044-0.0063 (0.071-0.087), the last layer's residual
#   scale on the experts' branch 0.0067-0.0091 (0.127-0.351) and held
#   experts' output projections 0.0071-0.0095 (0.127-0.371), the last
#   router's first layer 0.0077-0.0572 and gamma 0.0078-0.0488 (0.191-1.11
#   and 0.194-1.37: a router's gradient is a small difference of the
#   experts' outputs, so rounding weighs more on it and by seed).
# - tied_share: no reading of the precision (0.11-0.64) but a guard: a mask
#   that left nothing to compare would pass everything.
TIE_GAP = 0.002
TOLERANCE = {
    "logits_max": 0.06, "loss_abs": 0.008, "tied_share": 0.95,
    "grad_rel/embed/embedding": 0.025,
    "grad_rel/layer_0/attn/conv1_kernel": 0.04,
    "grad_rel/layer_0/attn/v_shift_proj/kernel": 0.025,
    "grad_rel/layer_last/ffn_join/branch_scale": 0.04,
    "grad_rel/layer_last/moe/router/fc1/kernel": 0.12,
    "grad_rel/layer_last/moe/router/gamma": 0.12,
    "grad_rel/layer_last/moe/experts/down_proj": 0.04}

# The leaves whose gradients the chip comparison reads (the whole tree in
# float32 would not fit beside the train state): a convolution's matrices
# and the shifted half of the values of the first layer (not the keys'
# temperature: a leaf of two numbers, each a sum that nearly cancels, so its
# relative error read 0.002 to 0.02 by seed on the chip and 0.28 at the CPU
# preset; the float32 tests hold it exactly); of the last layer, towards whose held experts the comparison's bias
# leans, the residual scale on the experts' branch, the router's first layer
# and its weight on the router before it, and the held experts' output
# projection (a held expert no row reaches has a zero gradient on both
# sides); and the embedding, whose gradient has the lookup's part and the
# head's.
GRAD_LEAVES = (("embed", "embedding"),
               ("layer_0", "attn", "conv1_kernel"),
               ("layer_0", "attn", "v_shift_proj", "kernel"),
               ("layer_last", "ffn_join", "branch_scale"),
               ("layer_last", "moe", "router", "fc1", "kernel"),
               ("layer_last", "moe", "router", "gamma"),
               ("layer_last", "moe", "experts", "down_proj"))

BIAS_UPDATE_RATE = 0.001   # assumed: this repo's noaux_tc rate

_HI = lax.Precision.HIGHEST


def _mm(a, b):
    return jnp.matmul(a, b, precision=_HI)


def rms_norm(x, scale, eps):
    return scale * x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def swiglu(x, p):
    gate = _mm(x, p["gate_proj"]["kernel"])
    up = _mm(x, p["up_proj"]["kernel"])
    return _mm(gate / (1.0 + jnp.exp(-gate)) * up, p["down_proj"]["kernel"])


def gelu(x):
    return 0.5 * x * (1.0 + lax.erf(x / jnp.sqrt(2.0)))


def rotary_settings(cfg):
    """``(theta, factor)``: the file's rotary group of its layers' kind."""
    group = cfg.get("rope_parameters", {})
    group = group.get(cfg.get("layer_types", ["hybrid"])[0], group)
    return (group.get("rope_theta", cfg.get("rope_theta")),
            group.get("partial_rotary_factor",
                      cfg.get("partial_rotary_factor", 1.0)))


def rope(x, theta, factor=1.0):
    """Half-split rotary embedding over the first ``factor`` of the last
    axis of [B, L, H, D]; the rest passes through."""
    turn = int(x.shape[-1] * factor)
    half = turn // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:turn]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., turn:]], -1)


def shifted(x, by):
    """``x[:, t - by]`` along axis 1, zeros before the first position."""
    if by == 0:
        return x
    return jnp.concatenate([jnp.zeros_like(x[:, :by]), x[:, :-by]], 1)


def cca(cfg, p, x, q_block=None):
    """Attention inside the compressed latent (the module's docstring)."""
    b, l, _ = x.shape
    h, g, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    rep = h // g
    theta, factor = rotary_settings(cfg)
    q0 = _mm(x, p["q_proj"]["kernel"]).reshape(b, l, h, hd)
    k0 = _mm(x, p["k_proj"]["kernel"]).reshape(b, l, g, hd)
    z = jnp.concatenate([q0, k0], 2)
    taps = p["conv0_kernel"].reshape(cfg["cca_time0"], h + g, hd)
    u = p["conv0_bias"] + sum(taps[j] * shifted(z, j)
                              for j in range(cfg["cca_time0"]))
    w = p["conv1_bias"] + sum(
        jnp.einsum("blhc,hcd->blhd", shifted(u, j), p["conv1_kernel"][:, j],
                   precision=_HI) for j in range(cfg["cca_time1"]))
    q = w[:, :, :h] + 0.5 * (q0 + jnp.repeat(k0, rep, 2))
    k = w[:, :, h:] + 0.5 * (q0.reshape(b, l, g, rep, hd).mean(3) + k0)

    def unit(t):
        return t / jnp.sqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-12) * (
            hd ** 0.5)

    q = rope(unit(q), theta, factor)
    k = rope(unit(k) * p["temperature"][:, None], theta, factor)
    v = jnp.concatenate(
        [_mm(x, p["v_proj"]["kernel"]),
         shifted(_mm(x, p["v_shift_proj"]["kernel"]), 1)], -1).reshape(
             b, l, g, hd)
    k, v = jnp.repeat(k, rep, 2), jnp.repeat(v, rep, 2)
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))
    kpos = jnp.arange(l)

    def rows(q_rows, first):
        s = jnp.einsum("bqhd,bkhd->bhqk", q_rows, k, precision=_HI) * scale
        qpos = first + jnp.arange(q_rows.shape[1])
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        s = s - s.max(-1, keepdims=True)
        e = jnp.exp(s)
        e = e / e.sum(-1, keepdims=True)
        return jnp.einsum("bhqk,bkhd->bqhd", e, v, precision=_HI)

    if q_block is None or q_block >= l:
        o = rows(q, 0)
    else:
        blocks = q.reshape(b, l // q_block, q_block, h, hd)
        o = lax.map(
            lambda a: jax.checkpoint(rows)(a[0], a[1]),
            (jnp.moveaxis(blocks, 1, 0), jnp.arange(0, l, q_block)))
        o = jnp.moveaxis(o, 0, 1).reshape(b, l, h, hd)
    return _mm(o.reshape(b, l, h * hd), p["o_proj"]["kernel"])


def router(cfg, p, x, before=None):
    """The router's distribution [.., E] over all experts and its state
    ``r``, which the next layer's router adds in."""
    r = _mm(x, p["down_proj"]["kernel"])
    if before is not None:
        r = r + p["gamma"] * before
    h = rms_norm(r, p["norm"]["scale"], cfg["rms_norm_eps"])
    for name in ("fc1", "fc2"):
        h = gelu(_mm(h, p[name]["kernel"]) + p[name]["bias"])
    s = _mm(h, p["out_proj"]["kernel"])
    s = s - s.max(-1, keepdims=True)
    e = jnp.exp(s)
    return e / e.sum(-1, keepdims=True), r


def expert_layer(cfg, p, bias, x, before=None, experts_held=None):
    """The expert layer's output, its router's state, its counts [E], each
    token's gate, and each token's margin: how far the picked expert's
    biased probability lies above the runner-up's.  A token whose margin is
    smaller than the rounding of the router's input is at a tie: a bf16
    program may route it otherwise than float32 does, and both are right.

    The router's width is the published count of experts; the experts of
    ``p`` are the held ones, or a larger stack that is sliced."""
    prob, r = router(cfg, p["router"], x, before)
    e_all = prob.shape[-1]
    first, count = experts_held or (0, e_all)
    top, idx = lax.top_k(prob + bias, 2)     # the bias selects, no more
    pick = idx[..., 0]
    gate = jnp.take_along_axis(prob, idx[..., :1], -1)
    stack = p["experts"]
    offset = first if stack["gate_proj"].shape[0] > count else 0
    y = jnp.zeros_like(x)
    for j in range(count):
        one = {name: {"kernel": stack[name][offset + j]}
               for name in ("gate_proj", "up_proj", "down_proj")}
        y = y + jnp.where((pick == first + j)[..., None], gate, 0.0) * swiglu(
            x, one)
    counts = (pick[..., None] == jnp.arange(e_all)).sum(
        tuple(range(pick.ndim)))
    return y, r, counts, gate[..., 0], top[..., 0] - top[..., 1]


def joined(p, x, branch):
    return ((p["stream_scale"] * x + p["stream_shift"])
            + (p["branch_scale"] * branch + p["branch_shift"]))


def embedding_of(params, vocab=None):
    table = params["embed"]["embedding"]
    return table if vocab is None else table[:vocab]


def hidden(cfg, params, bias, tokens, experts_held=None, vocab=None,
           q_block=None):
    """The rows the head reads [B, L, d] (after the final norm), the counts
    by layer, and each position's smallest ``expert_layer`` margin over the
    layers [B, L].

    ``bias``: {"layer_i": [E]}.  Each block is recomputed in the backward
    pass (``jax.checkpoint``): memory, not numbers."""
    eps = cfg["rms_norm_eps"]
    x = embedding_of(params, vocab)[tokens]
    counts, before = {}, None
    margin = jnp.full(tokens.shape, jnp.inf)
    for i in range(cfg["num_hidden_layers"]):
        def block(x, p, b, before):
            h = joined(p["attn_join"], x, cca(
                cfg, p["attn"], rms_norm(x, p["attn_norm"]["scale"], eps),
                q_block))
            y, r, c, _, m = expert_layer(
                cfg, p["moe"], b, rms_norm(h, p["ffn_norm"]["scale"], eps),
                before, experts_held)
            return joined(p["ffn_join"], h, y), r, c, m

        x, before, c, m = jax.checkpoint(block)(
            x, params[f"layer_{i}"], bias[f"layer_{i}"], before)
        counts[f"layer_{i}"] = c
        margin = jnp.minimum(margin, m)
    return (rms_norm(x, params["norm_f"]["scale"], eps), counts,
            lax.stop_gradient(margin))


def forward(cfg, params, bias, tokens, experts_held=None, vocab=None,
            q_block=None):
    """Logits [B, L, V], the auxiliary loss (none: 0), the counts by layer
    and the margins [B, L]."""
    rows, counts, margin = hidden(cfg, params, bias, tokens, experts_held,
                                  vocab, q_block)
    logits = jnp.einsum("bld,vd->blv", rows, embedding_of(params, vocab),
                        precision=_HI)
    return logits, jnp.float32(0.0), counts, margin


def _nll(logits, targets):
    m = logits.max(-1, keepdims=True)
    logp = logits - m - jnp.log(jnp.sum(jnp.exp(logits - m), -1,
                                        keepdims=True))
    return -jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]


def loss(logits, tokens, weight=None):
    """Mean next-token cross-entropy: position t predicts token t + 1.
    ``weight`` [B, L]: the mean is over the positions it marks."""
    nll = _nll(logits[:, :-1], tokens[:, 1:])
    if weight is None:
        return jnp.mean(nll)
    weight = weight[:, :-1].astype(nll.dtype)
    return jnp.sum(nll * weight) / jnp.sum(weight)


def _row_blocks(x, row_block):
    """[N, ...] as [N / row_block, row_block, ...]; one block for ``None``."""
    n = x.shape[0]
    block = n if row_block is None or row_block >= n else row_block
    return x.reshape((n // block, block) + x.shape[1:])


def loss_rows(rows, head, tokens, weight=None, row_block=None):
    """``loss`` of the logits ``rows head^T`` without the whole of them:
    ``row_block`` rows at a time, each block recomputed in the backward
    pass.  The last position of a sequence has no target: its weight is 0."""
    b, l, d = rows.shape
    weight = (jnp.ones((b, l)) if weight is None
              else weight.astype(jnp.float32)).at[:, -1].set(0.0)
    targets = jnp.roll(tokens, -1, 1)

    def block(args):
        x, t, w = args
        return jnp.sum(_nll(jnp.einsum("nd,vd->nv", x, head, precision=_HI),
                            t) * w)

    sums = lax.map(jax.checkpoint(block), tuple(
        _row_blocks(a.reshape((b * l,) + a.shape[2:]), row_block)
        for a in (rows, targets, weight)))
    return jnp.sum(sums) / jnp.sum(weight)


def logits_error(rows, head, want_rows, want_head, row_block=None):
    """Each position's largest logit error [B, L] between ``rows head^T``
    (the program's, in its own type) and ``want_rows want_head^T``, and the
    largest logit the second has, ``row_block`` rows at a time."""
    b, l, d = rows.shape

    def block(args):
        x, want_x = args
        got = jnp.einsum("nd,vd->nv", x, head,
                         preferred_element_type=jnp.float32)
        want = jnp.einsum("nd,vd->nv", want_x, want_head, precision=_HI)
        return (jnp.max(jnp.abs(got - want), -1),
                jnp.max(jnp.abs(want), -1))

    worst, top = lax.map(block, tuple(
        _row_blocks(a.reshape(b * l, d), row_block)
        for a in (rows, want_rows)))
    return worst.reshape(b, l), jnp.max(top)


def objective(cfg, params, bias, tokens, row_block=None, **kw):
    """What a step minimises: the cross-entropy (no auxiliary loss)."""
    rows, _, _ = hidden(cfg, params, bias, tokens, **kw)
    return loss_rows(rows, embedding_of(params, kw.get("vocab")), tokens,
                     None, row_block)


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
    """|got - want| / |want| (Frobenius); 0 where both are all zero (a held
    expert no row reached)."""
    err = jnp.sqrt(jnp.sum((got - want) ** 2))
    norm = jnp.sqrt(jnp.sum(want ** 2))
    return jnp.where(norm > 0, err / jnp.where(norm > 0, norm, 1.0),
                     jnp.where(err > 0, jnp.inf, 0.0))


def clear_of_ties(margin):
    """The positions [B, L] whose routing rounding cannot move: the picked
    expert's biased probability at least ``TIE_GAP`` above the runner-up's,
    in every layer."""
    return margin >= TIE_GAP


def agreement(worst, top, loss_value, want_loss, grads, want_grads, clear):
    """The measures ``TOLERANCE`` limits, as arrays.  ``worst`` [B, L] and
    ``top`` are ``logits_error``'s; ``clear`` [B, L] is ``clear_of_ties``
    of the reference's margins; both losses are means over those positions
    (``loss_rows(..., weight=clear)``), and ``grads`` and ``want_grads`` are
    ``grad_leaves`` of the objectives with those losses.
    ``logits_all_max`` and ``logits_all_p50``, over every position, are
    printed and not limited."""
    worst, clear = worst.reshape(-1), clear.reshape(-1)
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

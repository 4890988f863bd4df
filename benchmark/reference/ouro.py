"""Plain float32 reference: Ouro's looped decoder (ByteDance, "Scaling Latent
Reasoning via Looped Language Models", arXiv:2510.25741): a stack of blocks
applied ``total_ut_steps`` times over the same weights, an exit after every
pass, one gate that turns the exits into a distribution, and the first-stage
training loss weighted over them; forward, loss and, through ``jax.grad`` of
``objective``, gradients.

``jax.numpy`` only: no flax, no kernel, no cache, nothing from the program
but the names in its parameter tree (``models/decoder.py``).  A ``lax.scan``
over the passes (a loop with one body, so that the compiled reference is a
pass's size and not four; ``unroll`` lays the passes out one after another)
and a Python loop over the blocks; attention is explicit scores and a
softmax.  ``cfg`` is the configuration file (the catalog's key names).  Run
it under ``jax.default_matmul_precision("highest")``.

    block l:  a  = W_o Attn(rope(W_q n1(h)), rope(W_k n1(h)), W_v n1(h))
              h' = h + n2(a)
              m  = W_down(silu(W_gate n3(h')) * W_up n3(h'))
              h''= h' + n4(m)
    loop:     x_0 = Embed(tokens);  x_t = norm_f(Stack(x_{t-1})), t = 1..T
    exits:    s_t = x_t;  z_t = W_head s_t;  lam_t = sigmoid(w_g . s_t + b_g)
              p_1 = lam_1;  p_t = lam_t prod_{j<t}(1 - lam_j), t < T
              p_T = prod_{j<T}(1 - lam_j)
    loss:     mean over positions of sum_t p_t CE(z_t, y) - beta H(p)

- ``q_block``: attention computed for that many query rows at a time, each
  block recomputed in the backward pass; ``row_block``: an exit's logits and
  cross-entropy computed for that many rows at a time, likewise.  With
  ``jax.checkpoint`` around every block application this lets the backward
  pass at L = 8192 fit beside a train state.  Neither changes the numbers.

What the source's ``config.json`` does not say, each also under ``assumed``
in ``benchmark/configs/ouro-2.6b.json``: the four norms of a block and
``norm_f`` between passes (the checkpoint's ``modeling_ouro.py``), no biases
outside the gate, the gate (``Linear(d, 1)``, shared by the passes, reading
the normalised rows), ``beta`` 0.1, and half-split rotary pairs (dimension i
turns with i + 64).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

# What `correct` allows between the program under its bf16 policy and this
# reference, on the chip, at the published widths, six layers, four passes
# and 2 x 8,192 tokens, with the weights the run starts from.  Nothing in
# this model is discontinuous, so every position is compared.
#
# What is compared is what the timed step returns from its first call on
# the whole resident batch: its ``loss`` and ``loss_exit_t``, the gradient
# it took (AdamW's first moment after one step from zero is (1 - b1) g) and
# the change it made to the weights, the last three on ``GRAD_LEAVES``.
# The step returns no value a position, so the exits' logits and ``p`` are
# the timed model's forward pass on the batch's first sequence.
# ``agreement`` and ``step_agreement`` compute the measures.
#
# Each limit of TOLERANCE lies between two readings there, both in PERF.md
# 6 under PR 30 (and nowhere else, so that they cannot disagree): the
# largest the program gave over its seeds, and the smallest the program on
# 8-bit (e4m3) weights gave.
# - logits_max: a position's largest logit error over the largest logit,
#   the maximum over the four exits and all positions.  Twenty-four block
#   applications of bf16 products with float32 norms, softmax and
#   accumulation, the residual stream rounded to bf16 between blocks.
# - p_max: the largest error of an exit's probability at any position.  The
#   gate and the exit distribution are float32 on both sides, so this is
#   the hidden rows' rounding seen through a sigmoid.
# - loss_abs: the objective (weighted cross-entropies less beta H).  The
#   weights' precision hardly moves a mean over 16,382 targets (8-bit
#   weights read within a factor of two of the bf16 policy's worst), so no
#   limit lies between the two readings with room on both sides: it takes
#   the accepted LM cell's limit (reference/kimi_vl_a3b.py), which leaves
#   the first reading here 186 times of room and the worst of twelve four.
# - grad_rel/<leaf>, each of GRAD_LEAVES: |g - g_ref| / |g_ref| (Frobenius)
#   over the whole leaf: the first layer's W_q (the sum of four passes'
#   gradients, through 24 applications), the last layer's output
#   projection, norm_f's scale (through every pass and every exit) and the
#   gate (reached only through p: a small difference of the exits' losses,
#   so rounding weighs more on it).
TOLERANCE = {
    "logits_max": 0.1, "p_max": 0.03, "loss_abs": 0.008,
    "grad_rel/layer_0/attn/q_proj/kernel": 0.1,
    "grad_rel/layer_last/mlp/down_proj/kernel": 0.08,
    "grad_rel/exit_gate/kernel": 0.1,
    "grad_rel/norm_f/scale": 0.07}

# What only a step has, beside TOLERANCE's measures.
# - exit_loss_abs: the largest error of an exit's own mean cross-entropy
#   (the step's ``loss_exit_t``): a loss, so ``loss_abs``'s limit, which
#   here does lie between the two readings.
# - update_rel: |dw - dw_plain| / |dw_plain|, the worst of GRAD_LEAVES: the
#   change the step made to a leaf against ``adamw_first_step`` of the
#   gradient the step itself took.  Float32 on both sides, so the weights'
#   precision does not move it; what it reads is the rounding of w + dw
#   (norm_f's scale is 1 and moves by 1e-4: half a unit in the last place
#   is 3e-4 of that).  The limit lies between that and 1, which a state
#   left unchanged reads, with the more room above the reading: a rate or
#   a decay off by a tenth of the update reads 0.1.
STEP_TOLERANCE = {"exit_loss_abs": 0.008, "update_rel": 0.01}

# The leaves whose gradients the chip comparison reads (the whole tree in
# float32 would not fit beside the train state).
GRAD_LEAVES = (("layer_0", "attn", "q_proj", "kernel"),
               ("layer_last", "mlp", "down_proj", "kernel"),
               ("exit_gate", "kernel"),
               ("norm_f", "scale"))

BETA = 0.1   # assumed: the paper's first-stage entropy weight

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


def attention(cfg, p, x, q_block=None):
    """Causal multi-head attention, as many key and value heads as query
    heads, ``head_dim`` wide, rotary over the whole head."""
    b, l, _ = x.shape
    h, hd = cfg["num_attention_heads"], cfg["head_dim"]
    q, k, v = (_mm(x, p[name]["kernel"]).reshape(b, l, h, hd)
               for name in ("q_proj", "k_proj", "v_proj"))
    q, k = rope(q, cfg["rope_theta"]), rope(k, cfg["rope_theta"])
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))
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
        blocks = q.reshape(b, l // q_block, q_block, h, hd)
        o = lax.map(
            lambda a: jax.checkpoint(rows)(a[0], a[1]),
            (jnp.moveaxis(blocks, 1, 0), jnp.arange(0, l, q_block)))
        o = jnp.moveaxis(o, 0, 1).reshape(b, l, h, hd)
    return _mm(o.reshape(b, l, h * hd), p["o_proj"]["kernel"])


def block(cfg, p, x, q_block=None):
    """One block: a norm before and after each branch, four scales."""
    eps = cfg["rms_norm_eps"]
    a = attention(cfg, p["attn"], rms_norm(x, p["attn_norm"]["scale"], eps),
                  q_block)
    x = x + rms_norm(a, p["attn_out_norm"]["scale"], eps)
    m = swiglu(rms_norm(x, p["ffn_norm"]["scale"], eps), p["mlp"])
    return x + rms_norm(m, p["ffn_out_norm"]["scale"], eps)


def exit_distribution(gate_logits):
    """``gate_logits`` [T, ...] -> ``p`` [T, ...] and its entropy [...].
    The last pass's gate output is not used: the last exit takes what the
    earlier ones left."""
    lam = 1.0 / (1.0 + jnp.exp(-gate_logits[:-1]))
    stayed = jnp.cumprod(1.0 - lam, 0)          # prod_{j<=t}(1 - lam_j)
    before = jnp.concatenate([jnp.ones_like(stayed[:1]), stayed[:-1]], 0)
    p = jnp.concatenate([lam * before, stayed[-1:]], 0)
    safe = jnp.where(p > 0, p, 1.0)
    return p, -jnp.sum(p * jnp.log(safe), 0)


def forward(cfg, params, tokens, q_block=None, unroll=False):
    """Every exit's hidden rows ``s`` [T, B, L, d], the exit distribution
    ``p`` [T, B, L] and its entropy [B, L].  Each block application is
    recomputed in the backward pass (``jax.checkpoint``): memory, not
    numbers."""
    eps = cfg["rms_norm_eps"]

    def one_pass(x, _):
        for i in range(cfg["num_hidden_layers"]):
            x = jax.checkpoint(lambda x, p: block(cfg, p, x, q_block))(
                x, params[f"layer_{i}"])
        x = rms_norm(x, params["norm_f"]["scale"], eps)
        return x, x     # the next pass's input, and this pass's exit

    passes = cfg["total_ut_steps"]
    _, s = lax.scan(one_pass, params["embed"]["embedding"][tokens], None,
                    length=passes, unroll=passes if unroll else 1)
    gate = params["exit_gate"]
    z = jnp.sum(s * gate["kernel"][:, 0], -1) + gate["bias"][0]
    p, entropy = exit_distribution(z)
    return s, p, entropy


def logits(params, s):
    """``W_head s`` for hidden rows ``s`` [..., d]: every exit's logits at
    once, for sizes at which they fit."""
    return jnp.einsum("...d,vd->...v", s, params["head"]["weight"],
                      precision=_HI)


def cross_entropy(params, s, tokens, row_block=None):
    """Each exit's next-token cross-entropy at each position, [T, B, L-1]:
    position t predicts token t + 1.  ``row_block``: that many positions'
    logits at a time, recomputed in the backward pass."""
    head = params["head"]["weight"]
    rows, targets = s[..., :-1, :], tokens[:, 1:]
    t, b, l, d = rows.shape

    def nll(rows, targets):
        z = jnp.einsum("tbld,vd->tblv", rows, head, precision=_HI)
        m = z.max(-1, keepdims=True)
        logz = (m + jnp.log(jnp.sum(jnp.exp(z - m), -1, keepdims=True)))[
            ..., 0]
        true = jnp.take_along_axis(
            z, jnp.broadcast_to(targets, (t,) + targets.shape)[..., None],
            -1)[..., 0]
        return logz - true

    if row_block is None or row_block >= l:
        return nll(rows, targets)
    pad = (-l) % row_block
    rows = jnp.pad(rows, ((0, 0), (0, 0), (0, pad), (0, 0)))
    targets = jnp.pad(targets, ((0, 0), (0, pad)))
    n = (l + pad) // row_block
    out = lax.map(
        lambda a: jax.checkpoint(nll)(a[0], a[1]),
        (jnp.moveaxis(rows.reshape(t, b, n, row_block, d), 2, 0),
         jnp.moveaxis(targets.reshape(b, n, row_block), 1, 0)))
    return jnp.moveaxis(out, 0, 2).reshape(t, b, l + pad)[..., :l]


def loss(ce, p, entropy, beta=BETA):
    """The first-stage objective from each exit's cross-entropy [T, B, L-1]
    and the exit distribution (the last position has no target)."""
    weighted = jnp.sum(p[..., :-1] * ce, 0)
    return jnp.mean(weighted) - beta * jnp.mean(entropy)


def objective(cfg, params, tokens, beta=BETA, q_block=None, row_block=None,
              unroll=False):
    """What a step minimises; and ``(s, p, each exit's mean cross-entropy)``
    beside it."""
    s, p, entropy = forward(cfg, params, tokens, q_block, unroll)
    ce = cross_entropy(params, s, tokens, row_block)
    return loss(ce, p, entropy, beta), (s, p, jnp.mean(ce, (1, 2)))


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


def logits_error(got_rows, got_head, want_rows, want_head, row_block=None):
    """The largest logit error at any position of any exit, and the largest
    reference logit: the program's rows against its head as the program
    multiplies them (their own types, float32 accumulation), the reference's
    at the highest precision, ``row_block`` positions at a time so that
    neither side's [T, L, V] logits exist at once."""
    t, b, l, d = want_rows.shape

    def err(a):
        got, want = a
        z = jnp.einsum("tbld,vd->tblv", got, got_head,
                       preferred_element_type=jnp.float32)
        z_ref = jnp.einsum("tbld,vd->tblv", want, want_head, precision=_HI)
        return jnp.max(jnp.abs(z - z_ref)), jnp.max(jnp.abs(z_ref))

    if row_block is None or row_block >= l:
        return err((got_rows, want_rows))
    n = l // row_block
    worst, top = lax.map(err, tuple(
        jnp.moveaxis(x.reshape(t, b, n, row_block, d), 2, 0)
        for x in (got_rows, want_rows)))
    return jnp.max(worst), jnp.max(top)


def agreement(logits_worst, logits_top, p, want_p, loss_value, want_loss,
              grads, want_grads):
    """The measures ``TOLERANCE`` limits, as arrays.  ``logits_worst`` and
    ``logits_top`` are ``logits_error``'s; ``grads`` and ``want_grads`` are
    ``grad_leaves`` of the two objectives."""
    out = {"logits_max": logits_worst / logits_top,
           "p_max": jnp.max(jnp.abs(p - want_p)),
           "loss_abs": jnp.abs(loss_value - want_loss)}
    for name, want in want_grads.items():
        out["grad_rel/" + name] = _rel(grads[name], want)
    return out


def adamw_first_step(grad, weight, opt):
    """What AdamW's first step from zero moments adds to ``weight``: the
    bias-corrected moments are the gradient and its square, and matrices
    decay (``opt``: the configuration's ``optimizer`` group)."""
    change = grad / (jnp.abs(grad) + opt["eps"])
    if weight.ndim >= 2:
        change = change + opt["weight_decay"] * weight
    return -opt["lr"] * change


def step_agreement(exit_ce, want_exit_ce, grads, before, after, opt):
    """The measures ``STEP_TOLERANCE`` limits: a step's own cross-entropy
    of each exit against the reference's, and the change it made to
    ``grad_leaves`` of the weights (``before`` -> ``after``) against plain
    AdamW of the gradients ``grads`` it took."""
    return {
        "exit_loss_abs": jnp.max(jnp.abs(exit_ce - want_exit_ce)),
        "update_rel": jnp.max(jnp.stack([
            _rel(after[name] - before[name],
                 adamw_first_step(grads[name], before[name], opt))
            for name in grads]))}


def within_tolerance(measures, slack: float = 1.0, limits=None) -> bool:
    """Every limited measure within ``slack`` times its limit (``limits``:
    ``TOLERANCE``, and a step's with ``STEP_TOLERANCE`` beside it).  1 on
    the chip; the CPU tests' preset sums over a hundredth of the tokens and
    a thirtieth of the width, so that rounding averages out less, and they
    hold the bf16 policy inside twice the limits and 8-bit weights outside
    even those."""
    limits = TOLERANCE if limits is None else limits
    return all(float(measures[k]) <= slack * limits[k] for k in limits)

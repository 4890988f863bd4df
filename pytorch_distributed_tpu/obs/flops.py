"""Efficiency accounting: analytic per-step FLOPs/bytes models and MFU/HFU.

The obs layer (metrics.py) answers "how fast is each step"; this module
answers "how much of the hardware that speed represents".  For every
registered model family it builds an analytic ``StepCost`` — matmul/conv
core FLOPs for forward + backward + optimizer update, plus a rough HBM
bytes estimate — and divides achieved FLOP/s by the chip's peak:

- **MFU** uses *model* FLOPs: the algorithmically necessary work (the
  PaLM-appendix convention).  Recompute taxes do not inflate it.
- **HFU** uses *hardware* FLOPs: model FLOPs plus the rematerialization
  work the chips actually execute.  HFU ≥ MFU; the gap IS the recompute
  tax (e.g. ViT ``remat=True`` trades ~1/3 extra matmuls for activation
  residency — models/vit.py).  The fused loss (ops/fused_ce.py) adds
  none: it takes its gradient in the pass that has the logits.

Counting conventions (chosen to match XLA's ``cost_analysis()`` so the
analytic model can be cross-checked, tests/test_efficiency.py):

- one multiply-add = 2 FLOPs;
- convolutions exclude padded taps (XLA's HloCostAnalysis counts only
  valid kernel applications — border pixels cost less);
- backward = 2x forward for the matmul/conv core (dgrad + wgrad);
- the SGD update is ~6 FLOPs/param and is **replicated** on every device
  under data parallelism — ``StepCost.per_device_flops`` accounts for
  that when comparing against a per-device ``cost_analysis()`` figure;
- elementwise/transcendental work (BN, layernorm, softmax, rope) is NOT
  counted: it is a few percent of the core on these families, and XLA
  books transcendentals separately anyway.  Parity is asserted at +-10%.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any, Dict, Optional, Sequence

# --------------------------------------------------------------------- peaks
# Dense-matmul peak per chip, FLOP/s, at the framework's bf16 compute
# policy (f32 for the v2/v3 generation is half of these — close enough for
# a utilization denominator).  Keys match jax Device.device_kind prefixes.
PEAK_FLOPS_PER_CHIP: Dict[str, float] = {
    "tpu v2": 45e12,
    "tpu v3": 123e12,
    "tpu v4": 275e12,
    "tpu v5 lite": 197e12,   # v5e device_kind spells it out
    "tpu v5e": 197e12,
    "tpu v5p": 459e12,
    "tpu v6e": 918e12,
    "tpu v6 lite": 918e12,
}

# CPU-test fallback: a nominal per-"device" figure so MFU math stays finite
# and deterministic on the simulated CPU mesh (the number is a placeholder,
# not a measurement — CI asserts plumbing, never CPU utilization).
CPU_FALLBACK_PEAK = 50e9

# Per-chip HBM capacity, bytes.  The planner's feasibility pruning
# (plan/cost.py) rejects layouts whose predicted MemCost peak exceeds
# this; same device_kind-prefix keying as the FLOPs table.
HBM_BYTES_PER_CHIP: Dict[str, float] = {
    "tpu v2": 8e9,
    "tpu v3": 16e9,
    "tpu v4": 32e9,
    "tpu v5 lite": 16e9,
    "tpu v5e": 16e9,
    "tpu v5p": 95e9,
    "tpu v6e": 32e9,
    "tpu v6 lite": 32e9,
}
CPU_FALLBACK_HBM = 4e9

# Nominal aggregate ICI bandwidth per chip, bytes/s — a *scoring*
# denominator for predicted comm time (plan/cost.py), not a measurement;
# figures are the published per-chip interconnect aggregates.
LINK_BYTES_PER_CHIP: Dict[str, float] = {
    "tpu v2": 62.5e9,
    "tpu v3": 87.5e9,
    "tpu v4": 300e9,
    "tpu v5 lite": 200e9,
    "tpu v5e": 200e9,
    "tpu v5p": 600e9,
    "tpu v6e": 448e9,
    "tpu v6 lite": 448e9,
}
CPU_FALLBACK_LINK = 10e9

# Per-chip HBM *bandwidth*, bytes/s — the memory-roofline denominator
# (obs/stepattr.py): a phase whose achieved bytes/s approaches this while
# its FLOP/s sit far under the matmul peak is HBM-bound, not compute-bound.
# Published per-chip figures; same device_kind-prefix keying as above.
HBM_BW_PER_CHIP: Dict[str, float] = {
    "tpu v2": 700e9,
    "tpu v3": 900e9,
    "tpu v4": 1228e9,
    "tpu v5 lite": 819e9,
    "tpu v5e": 819e9,
    "tpu v5p": 2765e9,
    "tpu v6e": 1640e9,
    "tpu v6 lite": 1640e9,
}
CPU_FALLBACK_HBM_BW = 20e9


def device_peak_flops(device=None) -> float:
    """Peak FLOP/s for one chip.  ``PTD_TPU_PEAK_FLOPS`` overrides (chips
    this table predates, or a measured-roofline denominator).  A CPU
    device gets the placeholder; any other device that is not in the
    table is an error — a utilization against a made-up peak would read
    as a measurement."""
    if device is None:
        import jax

        device = jax.devices()[0]
    return chip_peak_flops(getattr(device, "device_kind", "") or "")


def _chip_table_lookup(table: Dict[str, float], kind: Optional[str],
                       fallback: float, env: str) -> float:
    """Shared device_kind-prefix lookup for the capability tables.
    ``kind=None`` stays jax-free (the planner's analytic path) and, like
    the CPU's own ``device_kind`` ("cpu"), gets the CPU placeholder; the
    env override wins over both.  Any other kind that is not in the table
    raises."""
    env_val = os.environ.get(env)
    if env_val:
        return float(env_val)
    kind = (kind or "cpu").lower()
    if kind == "cpu":
        return fallback
    for prefix, value in table.items():
        if kind.startswith(prefix):
            return value
    raise ValueError(
        f"device_kind {kind!r} is not in the chip tables (obs/flops.py); "
        f"add it with its published figure or set ${env}")


def chip_hbm_bytes(kind: Optional[str] = None) -> float:
    """Per-chip HBM bytes for a device_kind string (``PTD_TPU_HBM_BYTES``
    overrides); an absent or CPU kind gets the CPU placeholder."""
    return _chip_table_lookup(HBM_BYTES_PER_CHIP, kind, CPU_FALLBACK_HBM,
                              "PTD_TPU_HBM_BYTES")


def chip_link_bytes(kind: Optional[str] = None) -> float:
    """Nominal aggregate ICI bytes/s per chip (``PTD_TPU_LINK_BYTES``
    overrides)."""
    return _chip_table_lookup(LINK_BYTES_PER_CHIP, kind, CPU_FALLBACK_LINK,
                              "PTD_TPU_LINK_BYTES")


def chip_hbm_bw(kind: Optional[str] = None) -> float:
    """Per-chip HBM bandwidth, bytes/s (``PTD_TPU_HBM_BW`` overrides);
    an absent or CPU kind gets the CPU placeholder — roofline labels on
    the simulated mesh assert plumbing, never real intensity."""
    return _chip_table_lookup(HBM_BW_PER_CHIP, kind, CPU_FALLBACK_HBM_BW,
                              "PTD_TPU_HBM_BW")


def chip_peak_flops(kind: Optional[str] = None) -> float:
    """Peak FLOP/s per chip from a device_kind *string* — the jax-free twin
    of ``device_peak_flops`` the planner uses (``PTD_TPU_PEAK_FLOPS``
    overrides)."""
    return _chip_table_lookup(PEAK_FLOPS_PER_CHIP, kind, CPU_FALLBACK_PEAK,
                              "PTD_TPU_PEAK_FLOPS")


# ---------------------------------------------------------------- step costs
@dataclasses.dataclass(frozen=True)
class StepCost:
    """Per-optimizer-step cost of one registered model family config.

    ``model_flops``    algorithmic FLOPs (MFU numerator);
    ``hardware_flops`` incl. remat recompute (HFU numerator);
    ``bytes``          rough HBM traffic (params+grads+optimizer r/w and
                       activations twice) — an arithmetic-intensity hint,
                       not cross-checked;
    ``update_flops``   optimizer portion (replicated per device under DP);
    ``params``         parameter count the update estimate used.
    """

    model_flops: float
    hardware_flops: float
    bytes: float
    update_flops: float
    params: int
    breakdown: Dict[str, float]

    def per_device_flops(self, n_devices: int) -> float:
        """XLA-comparable per-device estimate: the forward/backward core is
        sharded over the mesh but the optimizer update runs replicated on
        every device (the declared-DP layout shardlint calls
        replicated-state)."""
        n = max(1, int(n_devices))
        return (self.hardware_flops - self.update_flops) / n + self.update_flops


_SGD_FLOPS_PER_PARAM = 6.0  # wd mul-add, momentum mul-add, lr mul + sub


def _valid_taps(size: int, k: int, stride: int, pad: int) -> int:
    """Sum over output positions of in-bounds kernel taps along one spatial
    dim — the XLA convolution convention (padded taps cost nothing)."""
    out = (size + 2 * pad - k) // stride + 1
    total = 0
    for o in range(out):
        start = o * stride - pad
        total += max(0, min(start + k, size) - max(start, 0))
    return total


class _Walk:
    """Accumulator the per-family shape walks share."""

    def __init__(self):
        self.fwd = 0.0        # forward core FLOPs per sample
        self.params = 0
        self.act_elts = 0.0   # activation elements produced per sample

    def conv(self, h, w, cin, cout, kh, kw, stride=1, pad=None, groups=1,
             bn=True):
        if pad is None:
            pad = kh // 2
        th = _valid_taps(h, kh, stride, pad)
        tw = _valid_taps(w, kw, stride, pad)
        ho = (h + 2 * pad - kh) // stride + 1
        wo = (w + 2 * pad - kw) // stride + 1
        self.fwd += 2.0 * cout * (cin / groups) * th * tw
        self.params += kh * kw * (cin // groups) * cout + (2 * cout if bn else 0)
        self.act_elts += ho * wo * cout
        return ho, wo

    def dense(self, n_rows, cin, cout, params=True):
        self.fwd += 2.0 * n_rows * cin * cout
        if params:
            self.params += cin * cout + cout
        self.act_elts += n_rows * cout


# ResNet-family table mirroring models/resnet.py's functools.partial zoo:
# (stage_sizes, block, groups, base_width).
_RESNET_CFGS: Dict[str, tuple] = {
    "resnet18": ([2, 2, 2, 2], "basic", 1, 64),
    "resnet34": ([3, 4, 6, 3], "basic", 1, 64),
    "resnet50": ([3, 4, 6, 3], "bottleneck", 1, 64),
    "resnet101": ([3, 4, 23, 3], "bottleneck", 1, 64),
    "resnet152": ([3, 8, 36, 3], "bottleneck", 1, 64),
    "wide_resnet50_2": ([3, 4, 6, 3], "bottleneck", 1, 128),
    "wide_resnet101_2": ([3, 4, 23, 3], "bottleneck", 1, 128),
    "resnext50_32x4d": ([3, 4, 6, 3], "bottleneck", 32, 4),
    "resnext101_32x8d": ([3, 4, 23, 3], "bottleneck", 32, 8),
}

# ViT table mirroring models/vit.py: (patch, d_model, layers, heads, mlp).
_VIT_CFGS: Dict[str, tuple] = {
    "vit_b_16": (16, 768, 12, 12, 3072),
    "vit_b_32": (32, 768, 12, 12, 3072),
    "vit_l_16": (16, 1024, 24, 16, 4096),
}


def _resnet_walk(arch: str, image_size: int, num_classes: int) -> _Walk:
    stage_sizes, block, groups, base_width = _RESNET_CFGS[arch]
    exp = 1 if block == "basic" else 4
    wk = _Walk()
    h, w = wk.conv(image_size, image_size, 3, 64, 7, 7, stride=2, pad=3)
    h, w = (h + 2 - 3) // 2 + 1, (w + 2 - 3) // 2 + 1  # maxpool 3x3 s2 p1
    c = 64
    for i, nblk in enumerate(stage_sizes):
        filt = 64 * 2 ** i
        for j in range(nblk):
            s = 2 if (i > 0 and j == 0) else 1
            if block == "basic":
                h2, w2 = wk.conv(h, w, c, filt, 3, 3, stride=s)
                wk.conv(h2, w2, filt, filt, 3, 3)
            else:
                width = int(filt * base_width / 64) * groups
                wk.conv(h, w, c, width, 1, 1, pad=0)
                h2, w2 = wk.conv(h, w, width, width, 3, 3, stride=s,
                                 groups=groups)
                wk.conv(h2, w2, width, filt * exp, 1, 1, pad=0)
            if c != filt * exp or s > 1:
                wk.conv(h, w, c, filt * exp, 1, 1, stride=s, pad=0)
            h, w, c = h2, w2, filt * exp
    wk.dense(1, c, num_classes)
    return wk


def _transformer_core(wk: _Walk, tokens: float, d: int, mlp: int,
                      seq: float) -> None:
    """One transformer block's matmul core for ``tokens`` rows attending
    over a ``seq``-long context (dense attention: causal masking does not
    reduce the einsums XLA emits)."""
    wk.dense(tokens, d, 3 * d, params=False)      # qkv
    wk.params += 3 * d * d                        # transformer.py: no bias
    wk.fwd += 4.0 * tokens * seq * d              # scores + weighted sum
    wk.act_elts += tokens * seq                   # score matrix (per head sum)
    wk.dense(tokens, d, d, params=False)          # proj
    wk.params += d * d
    wk.dense(tokens, d, mlp)                      # fc1
    wk.dense(tokens, mlp, d)                      # fc2
    wk.params += 4 * d                            # two layernorms


def _vit_walk(arch: str, image_size: int, num_classes: int) -> _Walk:
    patch, d, layers, _heads, mlp = _VIT_CFGS[arch]
    grid = image_size // patch
    tokens = grid * grid + 1  # + class token
    wk = _Walk()
    wk.dense(grid * grid, patch * patch * 3, d)   # patch embed
    wk.params += d + tokens * d                   # cls token + pos embeddings
    for _ in range(layers):
        _transformer_core(wk, tokens, d, mlp, tokens)
    wk.dense(1, d, num_classes)                   # head (class token only)
    return wk


def _finish(wk: _Walk, batch: int, recompute_fwd: float = 0.0,
            breakdown: Optional[Dict[str, float]] = None) -> StepCost:
    fwd = wk.fwd * batch
    update = _SGD_FLOPS_PER_PARAM * wk.params
    model = 3.0 * fwd + update
    hardware = model + recompute_fwd * batch
    # Rough bytes: params+grads+momentum r/w (f32) + activations twice
    # (produce in fwd, re-read in bwd) at 4 bytes — an intensity hint only.
    nbytes = 6.0 * 4 * wk.params + 2.0 * 4 * wk.act_elts * batch
    bd = {"forward": fwd, "backward": 2.0 * fwd, "update": update,
          "recompute": recompute_fwd * batch}
    if breakdown:
        bd.update(breakdown)
    return StepCost(model_flops=model, hardware_flops=hardware, bytes=nbytes,
                    update_flops=update, params=wk.params, breakdown=bd)


def image_step_cost(arch: str, batch: int, image_size: int,
                    num_classes: int = 1000, remat: bool = False) -> StepCost:
    """Analytic train-step cost for the image families with an analytic
    model (ResNet zoo + ViT).  Other archs raise — silently guessing a
    denominator would make MFU numbers lies."""
    if arch in _RESNET_CFGS:
        wk = _resnet_walk(arch, image_size, num_classes)
        recompute = 0.0
    elif arch in _VIT_CFGS:
        wk = _vit_walk(arch, image_size, num_classes)
        # nn.remat on every encoder block replays the block forwards in
        # backward: ~+1x forward of the block stack (the ~1/3-extra-matmul
        # tax noted at models/vit.py).
        recompute = wk.fwd if remat else 0.0
    else:
        raise ValueError(
            f"no analytic FLOPs model for arch {arch!r}; --mfu supports "
            f"{sorted(_RESNET_CFGS) + sorted(_VIT_CFGS)} (obs/flops.py)")
    return _finish(wk, batch, recompute_fwd=recompute)


def lm_step_cost(vocab_size: int, d_model: int, n_layers: int, batch: int,
                 seq_len: int, mlp_ratio: int = 4, fused_ce: bool = False,
                 remat: bool = False, moe_experts: int = 0,
                 moe_top_k: int = 1) -> StepCost:
    """Analytic train-step cost for the transformer-LM family.

    ``fused_ce``: the chunked tied-head+CE (ops/fused_ce.py) projects the
    ``seq_len - 1`` loss rows only, and runs the model's three head
    products a chunk (logits, dh, dE) in one loop: no hardware FLOPs
    beyond the model's; the replicated/dp/tp sharding variants all do the
    same global arithmetic.
    ``remat``: block rematerialization (+1x block-stack forward, hardware
    only).  The pipeline schedules (gpipe/1f1b/interleaved) run the same
    math as the plain stack, so no schedule parameter: FLOPs don't change,
    only the bubble does — and the bubble is a *time* effect MFU already
    sees through the step-time denominator."""
    d, T = d_model, batch * seq_len
    wk = _Walk()
    wk.params += vocab_size * d                   # tied embedding
    block_fwd0 = wk.fwd
    for _ in range(n_layers):
        if moe_experts > 1:
            wk.dense(T // batch, d, 3 * d, params=False)
            wk.params += 3 * d * d
            wk.fwd += 4.0 * (T // batch) * seq_len * d
            wk.dense(T // batch, d, d, params=False)
            wk.params += d * d
            # router + top_k expert MLPs per token; expert params stack E-wide
            wk.dense(T // batch, d, moe_experts, params=False)
            wk.params += d * moe_experts
            wk.fwd += moe_top_k * (2.0 * (T // batch) * d * mlp_ratio * d * 2)
            wk.params += moe_experts * (2 * d * mlp_ratio * d
                                        + mlp_ratio * d + d)
            wk.params += 4 * d
        else:
            _transformer_core(wk, T // batch, d, mlp_ratio * d, seq_len)
    wk.params += 2 * d                            # final layernorm
    block_fwd = wk.fwd - block_fwd0               # per-sample block stack
    # Head: tied embed.attend over the full sequence unfused; the fused
    # path projects only the seq_len-1 loss rows.
    head_rows = (seq_len - 1) if fused_ce else seq_len
    wk.dense(head_rows, d, vocab_size, params=False)
    return _finish(wk, batch, recompute_fwd=block_fwd if remat else 0.0)


def decoder_step_cost(config: Any, batch: int, seq_len: int,
                      fused_ce: bool = False) -> StepCost:
    """Analytic train-step cost for a configured decoder
    (``models/decoder.DecoderConfig``): latent, compressed-latent or plain
    heads, dense or expert feed-forward (a linear or an MLP router), an
    untied or a tied head, and a stack that runs ``total_ut_steps`` times
    with a head after every pass.  It counts applications, not parameters:
    a looped model pays ``passes x layers`` blocks and ``passes`` heads a
    token while its optimizer runs over one set of weights.

    The yardstick's conventions (``benchmark/flops_ouro.py``,
    ``flops_kimi_vl_a3b.py``, ``flops_zaya1.py``, held together by tests):
    causal attention is
    half the square, the routed experts are counted at the uniform
    expectation (``top_k * held / routed`` a token), every position's row
    meets the head, the exit gate's ``d`` a row and the depthwise
    convolution's few multiply-adds a channel are left out, a tied head is
    one product forward and one matrix of parameters.  ``remat`` adds the
    blocks' recomputed forward to the hardware count only; ``fused_ce``
    moves no count (the fused loss recomputes nothing, and every
    position's row is counted either way)."""
    c = config
    d, heads, seq = c.hidden_size, c.num_attention_heads, seq_len
    if c.kv_lora_rank:
        qk, vd = c.qk_nope_head_dim + c.qk_rope_head_dim, c.v_head_dim
        proj = (d * heads * qk + d * (c.kv_lora_rank + c.qk_rope_head_dim)
                + c.kv_lora_rank * heads * (c.qk_nope_head_dim + vd)
                + heads * vd * d)
        attn_params = proj + c.kv_lora_rank
    elif c.cca_time0:
        qk = vd = c.head_dim
        kv_heads = c.num_key_value_heads or heads
        stacked = (heads + kv_heads) * qk
        # W_q, W_k, the values' two halves, W_o, and a matrix a head and
        # a tap of the second convolution
        proj = (2 * d * heads * qk + 2 * d * kv_heads * qk
                + c.cca_time1 * stacked * qk)
        attn_params = (proj + (c.cca_time0 + 2) * stacked  # taps, biases
                       + kv_heads)                          # temperatures
    else:
        qk = vd = c.head_dim or d // heads
        proj = attn_params = 4 * d * heads * qk
    # a scaled join has four vectors where a plain one has none
    norms = (4 if c.sandwich_norm else 10 if c.cca_time0 else 2) * d
    dense = 3 * d * c.intermediate_size
    held = c.experts_held[1]
    expert = 3 * d * c.moe_intermediate_size
    r = c.router_hidden_size
    router = (d * r + 2 * r * r + r * c.n_routed_experts if r
              else d * c.n_routed_experts)
    expert_flops = (expert * c.n_shared_experts + router
                    + (expert * c.num_experts_per_tok * held
                       / c.n_routed_experts if held else 0.0))
    # an MLP router's norm and two biases; its gamma from the second on
    expert_params = (expert * (c.n_shared_experts + held) + router
                     + (3 * r if r else 0))
    passes, lead = c.total_ut_steps, c.first_k_dense_replace
    wk = _Walk()
    # embedding, head (the embedding again if tied), norm_f
    wk.params += (1 if c.tie_word_embeddings else 2) * c.vocab_size * d + d
    if passes > 1:
        wk.params += d + 1                          # the exit gate
    for i in range(c.num_hidden_layers):
        wk.params += attn_params + norms + (dense if i < lead
                                            else expert_params)
        if r and i > lead:
            wk.params += r
        ffn = dense if i < lead else expert_flops
        wk.fwd += passes * seq * (2.0 * (proj + ffn)
                                  + 2.0 * heads * (qk + vd) * seq / 2)
        wk.act_elts += passes * seq * d
    blocks = wk.fwd
    wk.fwd += passes * 2.0 * seq * d * c.vocab_size   # the heads
    return _finish(wk, batch, recompute_fwd=blocks if c.remat else 0.0)


def lm_step_cost_for(model: Any, batch: int, seq_len: int,
                     fused_ce_chunks: int = 0) -> StepCost:
    """Build the LM cost from a live model instance (TransformerLM or
    PipelinedTransformerLM — both carry the config attributes — or a
    configured ``DecoderLM``, from its ``config``)."""
    if hasattr(model, "config"):
        return decoder_step_cost(model.config, batch, seq_len,
                                 fused_ce=bool(fused_ce_chunks))
    n_layers = getattr(model, "n_layers", None)
    if n_layers is None:  # pipeline model: chunks x blocks-per-chunk
        n_layers = int(model.n_chunks) * int(model.n_blocks)
    remat = bool(getattr(model, "remat", False))
    if getattr(model, "has_manual_grads", lambda: False)():
        # 1F1B/interleaved stash stage *inputs* only and replay the stage
        # forward in backward — remat by construction.
        remat = True
    return lm_step_cost(
        vocab_size=int(model.vocab_size),
        d_model=int(model.d_model),
        n_layers=int(n_layers),
        batch=batch,
        seq_len=seq_len,
        fused_ce=bool(fused_ce_chunks),
        remat=remat,
        moe_experts=int(getattr(model, "moe_experts", 0) or 0),
        moe_top_k=int(getattr(model, "moe_top_k", 1) or 1),
    )


def xla_step_flops(jitted, *args) -> float:
    """Per-device FLOPs from the compiler's own cost model
    (``lower().compile().cost_analysis()``) — the cross-check oracle the
    analytic models are tested against (compare with
    ``StepCost.per_device_flops(n)``)."""
    analysis = jitted.lower(*args).compile().cost_analysis()
    if isinstance(analysis, (list, tuple)):
        analysis = analysis[0]
    return float(analysis["flops"])


# ----------------------------------------------------------- comm estimates
@dataclasses.dataclass(frozen=True)
class CommCost:
    """Analytic per-step collective payload bytes (per device), by kind.

    The comm-side twin of ``StepCost``: what the parallelism layout
    *should* move per optimizer step, cross-checked against the measured
    ledger (obs/comms.py) the same way FLOPs are fenced against
    ``cost_analysis()`` — tests/test_comms.py pins the residual at ±15%.
    """

    by_kind: Dict[str, float]
    breakdown: Dict[str, float]

    @property
    def total_bytes(self) -> float:
        return sum(self.by_kind.values())


def comm_residual_pct(predicted: float, measured: float) -> float:
    """Relative prediction error in percent (against the measurement)."""
    if not measured:
        return 0.0 if not predicted else float("inf")
    return 100.0 * abs(predicted - measured) / measured


def image_comm_bytes(params: int, dp: int = 4,
                     metric_scalars: int = 5) -> CommCost:
    """Pure-DP image train step: one gradient all-reduce per parameter
    leaf (f32) plus the handful of scalar loss/metric psums
    (train/steps.py's loss_and_metrics reductions).  ``dp == 1`` lowers
    no collectives at all."""
    if dp <= 1:
        return CommCost(by_kind={}, breakdown={})
    grad = 4.0 * params
    scalars = 4.0 * metric_scalars
    return CommCost(by_kind={"all-reduce": grad + scalars},
                    breakdown={"grad_sync": grad, "scalars": scalars})


def image_comm_bytes_compressed(
    leaf_sizes: Sequence[int],
    dp: int = 4,
    mode: str = "int8",
    block: Optional[int] = None,
    metric_scalars: int = 5,
) -> CommCost:
    """Explicit-collectives image step with compressed gradient sync
    (ops/qcomm.py).  Quantized modes lower the two-hop decomposition per
    parameter leaf: an all-to-all of the full padded int8/fp8 payload +
    f32 block scales (the reduce-scatter stage), then an all-gather of
    the re-quantized shards + scales.  Per-device result bytes per leaf,
    with ``(padded, nb) = qcomm.chunk_layout(size, dp, block)``:

    - all-to-all:  ``padded`` (1-byte payload) + ``4*dp*nb`` (scales)
    - all-gather:  ``padded``                  + ``4*dp*nb``

    so the per-kind totals need the *per-leaf* sizes — padding depends on
    each leaf, not the parameter sum.  ``bf16`` keeps the single
    all-reduce at 2 bytes/param; scalar count/metric psums stay f32."""
    from pytorch_distributed_tpu.ops import qcomm

    if dp <= 1:
        return CommCost(by_kind={}, breakdown={})
    scalars = 4.0 * metric_scalars
    if mode == "bf16":
        grad = 2.0 * sum(leaf_sizes)
        return CommCost(by_kind={"all-reduce": grad + scalars},
                        breakdown={"grad_sync": grad, "scalars": scalars})
    if mode not in qcomm.QUANTIZED_MODES:
        return image_comm_bytes(sum(leaf_sizes), dp=dp,
                                metric_scalars=metric_scalars)
    block = qcomm.DEFAULT_BLOCK if block is None else block
    a2a = ag = 0.0
    for size in leaf_sizes:
        padded, nb = qcomm.chunk_layout(int(size), dp, block)
        a2a += padded + 4.0 * dp * nb
        ag += padded + 4.0 * dp * nb
    return CommCost(
        by_kind={"all-to-all": a2a, "all-gather": ag, "all-reduce": scalars},
        breakdown={"grad_sync": a2a + ag, "scalars": scalars})


def image_comm_bytes_zero(
    leaf_sizes: Sequence[int],
    dp: int = 4,
    mode: str = "none",
    block: Optional[int] = None,
    metric_scalars: int = 5,
) -> CommCost:
    """Explicit-collectives image step under ``--zero wus`` weight-update
    sharding (parallel/zero.py): the gradient all-reduce splits into a
    reduce-scatter (grads -> owned 1/N chunk) and an all-gather (parameter
    delta -> full tree), per leaf.  With ``padded = chunk_layout(size, dp,
    block)[0]`` and ``e`` the wire element size (4 f32 / 2 bf16):

    - reduce-scatter: ``e * padded/dp`` per-device result bytes per leaf
    - all-gather:     ``e * padded``   per-device result bytes per leaf

    Wire parity (``zero_wire_parity``): by the EQuARX accounting
    (obs/comms.py) the pair puts ``2*(dp-1)/dp * e * padded`` on the wire —
    exactly the ring all-reduce's cost (padding aside), so WUS reclaims
    (N-1)/N of the optimizer+gradient memory at *equal* wire bytes.

    Quantized modes compose with the qcomm path: stage 1 is the same
    all-to-all as the compressed all-reduce and the delta all-gather
    carries the same quantized payload + scales the compressed stage 2
    would — so the estimate delegates to ``image_comm_bytes_compressed``
    (identical by-kind totals, different *semantics*: the gather moves
    lr-scaled deltas, not re-quantized gradient shards)."""
    from pytorch_distributed_tpu.ops import qcomm

    if dp <= 1:
        return CommCost(by_kind={}, breakdown={})
    if mode in qcomm.QUANTIZED_MODES:
        return image_comm_bytes_compressed(
            leaf_sizes, dp=dp, mode=mode, block=block,
            metric_scalars=metric_scalars)
    elem = 2.0 if mode == "bf16" else 4.0
    block = qcomm.DEFAULT_BLOCK if block is None else block
    rs = ag = 0.0
    for size in leaf_sizes:
        padded, _ = qcomm.chunk_layout(int(size), dp, block)
        rs += elem * padded / dp
        ag += elem * padded
    scalars = 4.0 * metric_scalars
    return CommCost(
        by_kind={"reduce-scatter": rs, "all-gather": ag,
                 "all-reduce": scalars},
        breakdown={"grad_sync": rs + ag, "scalars": scalars})


def comm_cost_wire_bytes(cost: CommCost, n: int) -> float:
    """Total wire bytes for an analytic ``CommCost`` under the EQuARX
    per-device accounting (obs/comms.py ``wire_bytes``) — the common
    currency for comparing layouts whose *result* bytes differ (an
    all-reduce returns the full tree, a reduce-scatter returns 1/N)."""
    from pytorch_distributed_tpu.obs.comms import wire_bytes

    return sum(wire_bytes(kind, b, n) for kind, b in cost.by_kind.items())


def zero_wire_parity(leaf_sizes: Sequence[int], dp: int = 4,
                     mode: str = "none",
                     block: Optional[int] = None) -> Dict[str, float]:
    """The WUS free-lunch check: reduce-scatter + all-gather wire bytes vs
    the one-hop all-reduce for the same gradient tree, same compression
    mode.  Returns ``{"zero": .., "replicated": .., "ratio": ..}``;
    ``ratio <= 1 + pad_overhead`` — tests pin it at ~1 (the ring
    all-reduce IS a reduce-scatter + all-gather, WUS just applies the
    optimizer between the hops)."""
    zero = comm_cost_wire_bytes(
        image_comm_bytes_zero(leaf_sizes, dp=dp, mode=mode, block=block,
                              metric_scalars=0), dp)
    if mode == "bf16":
        repl_cost = image_comm_bytes_compressed(
            leaf_sizes, dp=dp, mode="bf16", metric_scalars=0)
    elif mode == "none":
        repl_cost = image_comm_bytes(sum(int(s) for s in leaf_sizes),
                                     dp=dp, metric_scalars=0)
    else:
        repl_cost = image_comm_bytes_compressed(
            leaf_sizes, dp=dp, mode=mode, block=block, metric_scalars=0)
    repl = comm_cost_wire_bytes(repl_cost, dp)
    return {"zero": zero, "replicated": repl,
            "ratio": zero / repl if repl else 0.0}


def lm_comm_bytes(vocab_size: int, d_model: int, n_layers: int, batch: int,
                  seq_len: int, dp: int = 4, tp: int = 1,
                  fused_ce: bool = False, params: Optional[int] = None,
                  loss_scalars: int = 2) -> CommCost:
    """Transformer-LM train-step collective payload bytes per device.

    DP (``tp == 1``): the gradient all-reduce covers every parameter
    *plus one extra tied-embedding block* — the tied embed's gradient
    arrives as two separately-reduced pieces (the input-embedding
    scatter-add and the output-head ``embed.attend`` matmul transpose),
    so ``V*D`` is counted twice — plus ``loss_scalars`` scalar psums.

    TP (Megatron-style tensor parallelism over a ``dp x tp`` mesh, with
    ``act = (batch/dp) * seq * d_model * 4`` bytes — the per-data-shard
    activation block):

    - 2 forward psums per layer (attn proj out, fc2 out) and 2 backward
      psums per layer (qkv input grad, fc1 input grad): ``4*L*act``;
    - head-sharded attention boundary: 2 permutes of ``act`` forward +
      2 of ``act/2`` backward = ``3*act`` collective-permute bytes;
    - vocab-sharded tied embedding: gather psum ``act`` forward +
      scatter-add psum ``act/2`` backward;
    - gradient sync over the data axis at the *sharded* parameter size:
      ``4*(params + V*D)/tp``.

    The fused-CE chunk loop's per-chunk scalar pmax/psum/pmin carries are
    a few hundred bytes and not modeled.  ``params`` defaults to the
    analytic ``lm_step_cost`` count for the same config."""
    if params is None:
        params = lm_step_cost(vocab_size, d_model, n_layers, batch,
                              seq_len).params
    grad_synced = 4.0 * (params + vocab_size * d_model)
    scalars = 4.0 * loss_scalars
    if tp <= 1:
        if dp <= 1:
            return CommCost(by_kind={}, breakdown={})
        return CommCost(
            by_kind={"all-reduce": grad_synced + scalars},
            breakdown={"grad_sync": grad_synced, "scalars": scalars})
    act = (batch / max(1, dp)) * seq_len * d_model * 4.0
    tp_psums = 4.0 * n_layers * act
    embed = 1.5 * act
    permutes = 3.0 * n_layers * act
    grad = grad_synced / tp
    allreduce = grad + tp_psums + embed + scalars
    return CommCost(
        by_kind={"all-reduce": allreduce, "collective-permute": permutes},
        breakdown={"grad_sync": grad, "tp_psums": tp_psums, "embed": embed,
                   "head_permutes": permutes, "scalars": scalars})


# ----------------------------------------------------------- memory estimates
@dataclasses.dataclass(frozen=True)
class MemCost:
    """Analytic per-device peak-HBM model for one train step.

    The memory-side twin of ``CommCost``: what the state layout and
    activation schedule *should* keep resident at the step's high-water
    mark, cross-checked against the static ledger (obs/memory.py) the
    same way comm estimates are fenced against the measured ledger —
    tests/test_memory.py pins the residual at ±15%.

    The accounting deliberately mirrors ``memory_analysis()``'s naive
    temp + argument + output sum (donated buffers counted on both sides)
    so the number is comparable to both the ledger and the compiler.
    """

    argument_bytes: float
    output_bytes: float
    temp_bytes: float
    breakdown: Dict[str, float]

    @property
    def peak_bytes(self) -> float:
        return self.argument_bytes + self.output_bytes + self.temp_bytes


# Same fence arithmetic for memory as for comms — re-exported under the
# name the memory tests read.
mem_residual_pct = comm_residual_pct


def train_mem_peak(param_bytes: float, act_bytes: float,
                   data_bytes: float = 0.0, *, dp: int = 4,
                   zero: bool = False, explicit_sync: bool = True,
                   metric_bytes: float = 128.0) -> MemCost:
    """Generic train-step peak-HBM model from first principles:

    - **arguments**: params + momentum + the per-device batch shard.
      Under ``--zero wus`` the momentum tree lives as owned 1/dp chunks.
    - **outputs**: the new state (same layout) + the scalar metrics
      tuple.  Donation aliases outputs onto arguments, but the compiler's
      accounting (and so the ledger's) books both sides — so does this.
    - **temps**: the gradient tree (one param-tree copy, live from
      backward until the update consumes it) + the live activation /
      saved-residual bytes at the backward peak.  ``explicit_sync`` adds
      the hand-written grad-sync path's materialized scratch: one synced
      tree for the all-reduce (or the gathered delta under zero), plus
      the owned-chunk stack between the reduce-scatter and all-gather
      hops.  GSPMD steps sync in place — pass ``explicit_sync=False``.
    """
    dp = max(1, int(dp))
    momentum = param_bytes / dp if zero else param_bytes
    state = param_bytes + momentum
    grads = param_bytes
    sync = 0.0
    if explicit_sync and dp > 1:
        sync = param_bytes + (param_bytes / dp if zero else 0.0)
    temp = grads + act_bytes + sync
    return MemCost(
        argument_bytes=state + data_bytes,
        output_bytes=state + metric_bytes,
        temp_bytes=temp,
        breakdown={"params": param_bytes, "momentum": momentum,
                   "data": data_bytes, "grads": grads,
                   "activations": act_bytes, "grad_sync_scratch": sync,
                   "metrics": metric_bytes})


def lm_act_bytes(d_model: int, n_layers: int, n_heads: int, batch: int,
                 seq_len: int, vocab_size: int, *, dp: int = 4,
                 mlp_ratio: int = 4, elem: float = 4.0) -> float:
    """Live activation/saved-residual bytes at the LM backward peak, per
    device (``b = batch/dp`` rows).  Per layer per token the autodiff
    schedule stashes ~9 d-wide tensors (ln1, qkv, attn out, proj out,
    two residual adds, ln2, fc2 out) + 2 mlp-wide (fc1 out, gelu out) +
    the two [H, T, T] score/softmax matrices; the loss head holds the
    logits block plus ~2x for log-softmax and its gradient."""
    b = batch / max(1, int(dp))
    per_token = 9.0 * d_model + 2.0 * mlp_ratio * d_model
    scores = 2.0 * n_heads * seq_len
    stack = b * seq_len * n_layers * (per_token + scores)
    head = 3.0 * b * seq_len * vocab_size
    return elem * (stack + head)


def lm_train_mem_peak(vocab_size: int, d_model: int, n_layers: int,
                      n_heads: int, batch: int, seq_len: int, *,
                      dp: int = 4, zero: bool = False,
                      mlp_ratio: int = 4) -> MemCost:
    """Analytic peak HBM for the GSPMD transformer-LM train step: tied
    embedding + block stack params (f32), momentum (1/dp-sharded under
    ``--zero wus``), the lm_act_bytes schedule, int32 token shard.
    GSPMD derives the grad sync in place, so no explicit scratch term."""
    params = lm_step_cost(vocab_size, d_model, n_layers, batch,
                          seq_len, mlp_ratio=mlp_ratio).params
    act = lm_act_bytes(d_model, n_layers, n_heads, batch, seq_len,
                       vocab_size, dp=dp, mlp_ratio=mlp_ratio)
    tokens = 4.0 * (batch / max(1, dp)) * seq_len + 8.0  # int32 + lr/step
    return train_mem_peak(4.0 * params, act, data_bytes=tokens, dp=dp,
                          zero=zero, explicit_sync=False,
                          metric_bytes=256.0)


# ------------------------------------------------------------------ reporter
class MFUReporter:
    """Turns host-measured step seconds into per-step MFU/HFU fields for
    the metrics JSONL (all-host math — never touches the device)."""

    def __init__(self, cost: StepCost, n_devices: int,
                 peak_per_chip: Optional[float] = None):
        self.cost = cost
        self.n_devices = max(1, int(n_devices))
        self.peak = (peak_per_chip if peak_per_chip is not None
                     else device_peak_flops())
        self._denom = self.peak * self.n_devices

    def fields(self, step_time: float) -> Dict[str, float]:
        dt = max(float(step_time), 1e-9)
        return {
            "mfu": 100.0 * self.cost.model_flops / dt / self._denom,
            "hfu": 100.0 * self.cost.hardware_flops / dt / self._denom,
            "model_tflops": self.cost.model_flops / dt / 1e12,
        }

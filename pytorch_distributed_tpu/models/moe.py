"""Mixture-of-Experts MLP with expert parallelism over an ``expert`` mesh axis.

Beyond-reference capability completing the framework's parallelism menu
(dp / tp / sp / **ep**).  Switch-Transformer-style top-1 routing (or
GShard/Mixtral-style top-k with renormalized gates, ``top_k > 1``) with a
capacity limit, expressed as dense dispatch/combine einsums — the
GSPMD-idiomatic formulation: expert parameters are stacked on a leading
``E`` axis and sharded ``P('expert', …)``; XLA lowers the dispatch einsum to
the all-to-all token exchange across the expert axis.  No hand-written
routing collectives.

The router's auxiliary load-balancing loss (Switch eq. 4: ``E · Σ_e f_e·p_e``)
is recorded via ``self.sow("losses", …)``; the LM step collects it with
``mutable=["losses"]`` and adds it to the objective.
"""

from __future__ import annotations

import functools
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp


class _FFN(nn.Module):
    d_model: int
    d_hidden: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        h = nn.Dense(self.d_hidden, dtype=self.dtype, name="fc1")(x)
        h = nn.gelu(h)
        return nn.Dense(self.d_model, dtype=self.dtype, name="fc2")(h)


class MoEMLP(nn.Module):
    n_experts: int
    capacity_factor: float = 1.25
    aux_coef: float = 0.01
    dtype: Any = jnp.float32
    # 1 = Switch (gate = raw top prob); >1 = GShard/Mixtral-style top-k with
    # renormalized gates and sequential capacity (first choices queue first).
    top_k: int = 1

    @nn.compact
    def __call__(self, x):
        B, L, C = x.shape
        E = self.n_experts
        S = B * L
        k = min(self.top_k, E)
        cap = max(1, int(self.capacity_factor * k * S / E))
        tokens = x.reshape(S, C)

        # Router runs in f32 (standard for stability).
        logits = nn.Dense(E, dtype=jnp.float32, name="router")(
            tokens.astype(jnp.float32)
        )
        probs = jax.nn.softmax(logits, axis=-1)                  # [S, E]
        topk_probs, topk_idx = jax.lax.top_k(probs, k)           # [S, k]
        if k == 1:
            gates = topk_probs                                   # Switch
        else:
            gates = topk_probs / jnp.maximum(
                topk_probs.sum(-1, keepdims=True), 1e-9
            )

        # Dispatch/combine accumulated choice-by-choice: choice c's tokens
        # take queue positions after all kept earlier-choice tokens (the
        # priority ordering GShard prescribes).
        dispatch = jnp.zeros((S, E, cap), jnp.float32)
        combine = jnp.zeros((S, E, cap), jnp.float32)
        counts = jnp.zeros((E,), jnp.float32)
        for c in range(k):
            onehot = jax.nn.one_hot(topk_idx[:, c], E, dtype=jnp.float32)
            pos = (jnp.cumsum(onehot, axis=0) - 1.0) + counts[None, :]
            pos_in_expert = jnp.sum(pos * onehot, axis=-1).astype(jnp.int32)
            keep = (pos_in_expert < cap).astype(jnp.float32)
            d_c = (
                onehot[:, :, None]
                * jax.nn.one_hot(pos_in_expert, cap, dtype=jnp.float32)[:, None, :]
                * keep[:, None, None]
            )                                                     # [S, E, cap]
            dispatch = dispatch + d_c
            combine = combine + d_c * gates[:, c][:, None, None]
            counts = counts + jnp.sum(onehot * keep[:, None], axis=0)

        # Aux loss (Switch eq. 4) on the first-choice assignment.
        frac = jnp.mean(
            jax.nn.one_hot(topk_idx[:, 0], E, dtype=jnp.float32), axis=0
        )
        imp = jnp.mean(probs, axis=0)
        self.sow("losses", "moe_aux", self.aux_coef * E * jnp.sum(frac * imp))

        expert_in = jnp.einsum(
            "sec,sd->ecd", dispatch, tokens.astype(jnp.float32)
        ).astype(self.dtype)                                      # [E, cap, C]

        experts = nn.vmap(
            _FFN,
            in_axes=0, out_axes=0,
            variable_axes={"params": 0},   # stacked params, leading E axis
            split_rngs={"params": True},
            metadata_params={nn.PARTITION_NAME: "expert"},
        )(d_model=C, d_hidden=4 * C, dtype=self.dtype, name="experts")
        expert_out = experts(expert_in)                           # [E, cap, C]

        out = jnp.einsum(
            "sec,ecd->sd", combine, expert_out.astype(jnp.float32)
        )
        return out.reshape(B, L, C).astype(x.dtype)


def moe_specs(params, expert_axis: str = "expert"):
    """PartitionSpec tree: expert-stacked params sharded on their leading
    axis; everything else replicated.  Compose with tp.py's ``state_specs``."""
    from jax.sharding import PartitionSpec as P

    def spec(path, leaf):
        names = [getattr(k, "key", str(k)) for k in path]
        if "experts" in names:
            return P(expert_axis, *([None] * (leaf.ndim - 1)))
        return P()

    return jax.tree_util.tree_map_with_path(spec, params)


# ---------------------------------------------------------------------------
# Dropless routing for the configured decoder (models/decoder.py): sorted
# (token, choice) pairs and grouped matrix products over the experts held.
# ---------------------------------------------------------------------------

# Sorted (token, expert) pairs a pass of the grouped products works on: a
# pass's activations are at most this many rows of d_model + 3 x width
# floats (51 MB a thousand rows at 2048 | 1408), whatever the imbalance.
GMM_CHUNK_ROWS = 8192

_TGMM = jax.lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(((0,), (0,)), ((), ())),
    lhs_ragged_dimensions=[0], rhs_group_dimensions=[])


def _gmm(rows, stack, sizes):
    """Rows of group g times ``stack[g]``: [C, a] x [G, a, b] -> [C, b]."""
    return jax.lax.ragged_dot(rows, stack, sizes,
                              preferred_element_type=jnp.float32)


def _tgmm(rows, cot, sizes):
    """Per group, rows^T cot: [C, a], [C, b] -> [G, a, b] (float32)."""
    return jax.lax.ragged_dot_general(rows, cot, sizes, _TGMM,
                                      preferred_element_type=jnp.float32)


def _chunks(token, gate, sizes, chunk_rows: int):
    """The sorted pairs cut into chunks of ``chunk_rows``: how many chunks
    hold a pair, and a function from a chunk's number to its token ids,
    gates, rows per group and the mask of rows that are pairs."""
    n = sizes.sum()
    pad = (-token.shape[0]) % chunk_rows
    token = jnp.pad(token, (0, pad))
    gate = jnp.pad(gate, (0, pad))
    ends = jnp.cumsum(sizes)
    starts = ends - sizes

    def chunk(c):
        lo = c * chunk_rows
        tok = jax.lax.dynamic_slice(token, (lo,), (chunk_rows,))
        g = jax.lax.dynamic_slice(gate, (lo,), (chunk_rows,))
        in_chunk = (jnp.clip(ends, lo, lo + chunk_rows)
                    - jnp.clip(starts, lo, lo + chunk_rows))
        valid = lo + jnp.arange(chunk_rows) < n
        return lo, tok, g, in_chunk.astype(jnp.int32), valid

    return (n + chunk_rows - 1) // chunk_rows, chunk


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def grouped_swiglu(x, token, gate, sizes, w_gate, w_up, w_down,
                   chunk_rows: int, dtype):
    """``y[t] = sum over the sorted pairs (t, e) of gate * SwiGLU_e(x[t])``
    and the rows the grouped products processed.

    ``token`` [N] and ``gate`` [N] list the pairs sorted by held expert,
    those on no held expert last; ``sizes`` [G] counts each held expert's
    pairs.  The pairs are worked through in chunks of ``chunk_rows`` by a
    loop that runs as many times as there are pairs to work on (a dynamic
    trip count: group sizes vary from step to step, shapes do not), so no
    pair is dropped however uneven the routing, and memory is bounded by
    one chunk.  The loop cannot be differentiated, hence the custom VJP,
    whose backward pass is the same loop."""
    return _grouped_fwd(x, token, gate, sizes, w_gate, w_up, w_down,
                        chunk_rows, dtype)[0]


def _grouped_fwd(x, token, gate, sizes, w_gate, w_up, w_down, chunk_rows,
                 dtype):
    wg, wu, wd = (w.astype(dtype) for w in (w_gate, w_up, w_down))
    n_chunks, chunk = _chunks(token, gate, sizes, chunk_rows)

    def body(c, carry):
        y, rows = carry
        _, tok, g, in_chunk, valid = chunk(c)
        xs = x[tok].astype(dtype)
        hidden = (jax.nn.silu(_gmm(xs, wg, in_chunk))
                  * _gmm(xs, wu, in_chunk)).astype(dtype)
        out = _gmm(hidden, wd, in_chunk) * g[:, None]
        # rows past the last group are not written by the grouped product
        out = jnp.where(valid[:, None], out, 0.0)
        return y.at[tok].add(out), rows + in_chunk.sum()

    y, rows = jax.lax.fori_loop(
        0, n_chunks, body,
        (jnp.zeros(x.shape, jnp.float32), jnp.int32(0)))
    return (y, rows), (x, token, gate, sizes, w_gate, w_up, w_down)


def _grouped_bwd(chunk_rows, dtype, res, cts):
    x, token, gate, sizes, w_gate, w_up, w_down = res
    dy = cts[0]
    wg, wu, wd = (w.astype(dtype) for w in (w_gate, w_up, w_down))
    wg_t, wu_t, wd_t = (w.transpose(0, 2, 1) for w in (wg, wu, wd))
    n_chunks, chunk = _chunks(token, gate, sizes, chunk_rows)

    def body(c, carry):
        dx, dgate, dwg, dwu, dwd = carry
        lo, tok, g, in_chunk, valid = chunk(c)
        keep = valid[:, None]
        xs = x[tok].astype(dtype)
        a, b = _gmm(xs, wg, in_chunk), _gmm(xs, wu, in_chunk)
        sig = jax.nn.sigmoid(a)
        hidden = (a * sig * b).astype(dtype)
        dout = jnp.where(keep, dy[tok], 0.0)
        out = jnp.where(keep, _gmm(hidden, wd, in_chunk), 0.0)
        dg = jnp.sum(out * dout, -1)
        dscaled = (dout * g[:, None]).astype(dtype)
        dhidden = _gmm(dscaled, wd_t, in_chunk)
        da = (dhidden * b * sig * (1.0 + a * (1.0 - sig))).astype(dtype)
        db = (dhidden * a * sig).astype(dtype)
        dxs = jnp.where(keep, _gmm(da, wg_t, in_chunk)
                        + _gmm(db, wu_t, in_chunk), 0.0)
        return (dx.at[tok].add(dxs),
                jax.lax.dynamic_update_slice(dgate, dg, (lo,)),
                dwg + _tgmm(xs, da, in_chunk),
                dwu + _tgmm(xs, db, in_chunk),
                dwd + _tgmm(hidden, dscaled, in_chunk))

    padded = token.shape[0] + (-token.shape[0]) % chunk_rows
    dx, dgate, dwg, dwu, dwd = jax.lax.fori_loop(
        0, n_chunks, body,
        (jnp.zeros(x.shape, jnp.float32), jnp.zeros((padded,), jnp.float32),
         jnp.zeros(w_gate.shape, jnp.float32),
         jnp.zeros(w_up.shape, jnp.float32),
         jnp.zeros(w_down.shape, jnp.float32)))
    return (dx.astype(x.dtype), None,
            dgate[:token.shape[0]].astype(gate.dtype), None,
            dwg.astype(w_gate.dtype), dwu.astype(w_up.dtype),
            dwd.astype(w_down.dtype))


grouped_swiglu.defvjp(_grouped_fwd, _grouped_bwd)


class _SwiGLU(nn.Module):
    """``(silu(x W_gate) * (x W_up)) W_down``, no biases."""

    width: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        def dense(features, name):
            return nn.Dense(features, use_bias=False, dtype=self.dtype,
                            name=name)

        hidden = nn.silu(dense(self.width, "gate_proj")(x)) * dense(
            self.width, "up_proj")(x)
        return dense(x.shape[-1], "down_proj")(hidden)


class _ExpertStack(nn.Module):
    """The held experts' SwiGLU weights, stacked on a leading axis."""

    count: int
    width: int

    @nn.compact
    def __call__(self, d_model: int):
        init = nn.initializers.lecun_normal(in_axis=-2, out_axis=-1,
                                            batch_axis=(0,))
        return {name: self.param(name, init, (self.count,) + shape,
                                 jnp.float32)
                for name, shape in (("gate_proj", (d_model, self.width)),
                                    ("up_proj", (d_model, self.width)),
                                    ("down_proj", (self.width, d_model)))}


class _RouterMLP(nn.Module):
    """A router that is an MLP with a state, all of it float32 (products at
    the highest precision: a score's rounding moves a token to another
    expert).  ``r = W_d x``; from the second expert layer on
    ``r += gamma * r_before``, the state of the router before it (``gamma``
    learned, 0 at the start); scores are ``softmax(W_3 gelu(W_2 gelu(W_1
    norm(r) + b_1) + b_2))``.  Returns the scores [S, E] and ``r``, the
    state for the next router."""

    n_routed: int
    hidden: int
    eps: float

    @nn.compact
    def __call__(self, x, before):
        from pytorch_distributed_tpu.models.decoder import RMSNorm

        def dense(features, name, use_bias):
            return nn.Dense(features, use_bias=use_bias, dtype=jnp.float32,
                            precision=jax.lax.Precision.HIGHEST, name=name)

        r = dense(self.hidden, "down_proj", False)(x.astype(jnp.float32))
        if before is not None:
            r = r + self.param("gamma", nn.initializers.zeros,
                               (self.hidden,), jnp.float32) * before
        h = RMSNorm(self.eps, name="norm")(r)
        for name in ("fc1", "fc2"):
            h = nn.gelu(dense(self.hidden, name, True)(h), approximate=False)
        return jax.nn.softmax(dense(self.n_routed, "out_proj", False)(h)), r


class RoutedExperts(nn.Module):
    """An expert layer that is told which experts it holds, and which of
    two scorers it was given.

    ``router_hidden`` = 0: scores are sigmoids of one linear map over all
    ``n_routed`` experts, in float32.  ``router_hidden`` > 0: scores are
    the softmax of a ``_RouterMLP`` of that width, which takes the state of
    the router before it (the call's second argument) and hands on its own
    (the call then returns the rows and that state).  Either
    way the ``top_k`` are chosen by score plus a selection bias that is
    state (the ``router`` collection), not a parameter; gates are the chosen
    scores, normalised if ``norm_topk_prob`` (never with one expert a token:
    the gate would be 1 and the router would learn nothing) and scaled.
    The sum runs over those of the chosen whose expert is one of ``held =
    (first, count)``; the shared expert (one SwiGLU of ``n_shared`` times
    the width, if any) is computed in full.  No capacity and no dropped
    token: see ``grouped_swiglu``.

    Sows the sequence-wise balance loss into ``losses`` and, into
    ``counters``, the step's counts of tokens by expert (all ``n_routed``:
    the bias update reads them), ``routed_here`` (pairs on held experts),
    ``rows_grouped`` (rows the grouped products processed) and
    ``rows_max`` (the fullest held expert's rows); with the MLP scorer also
    ``gate_mean`` (the mean gate) and ``router_entropy`` (the mean entropy
    of a token's scores)."""

    n_routed: int
    top_k: int
    width: int
    held: Tuple[int, int]
    n_shared: int = 0
    scaling: float = 1.0
    norm_topk_prob: bool = True
    seq_aux_alpha: float = 0.0
    router_hidden: int = 0
    eps: float = 1e-5
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, router_state=None):
        from pytorch_distributed_tpu.obs.trace import scope

        B, L, C = x.shape
        E, K = self.n_routed, self.top_k
        first, count = self.held
        tokens = x.reshape(B * L, C)
        with scope("moe_route"):
            bias = self.variable("router", "e_score_correction_bias",
                                 jnp.zeros, (E,), jnp.float32)
            if self.router_hidden:
                with scope("router_mlp"):
                    scores, router_state = _RouterMLP(
                        E, self.router_hidden, self.eps, name="router")(
                            tokens, router_state)
            else:
                scores = jax.nn.sigmoid(nn.Dense(
                    E, use_bias=False, dtype=jnp.float32, name="router")(
                        tokens.astype(jnp.float32)))              # [S, E]
            _, idx = jax.lax.top_k(scores + bias.value, K)        # [S, K]
            gates = jnp.take_along_axis(scores, idx, -1)
            if self.norm_topk_prob:
                gates = gates / (gates.sum(-1, keepdims=True) + 1e-20)
            gates = gates * self.scaling
            chose = jax.nn.one_hot(idx, E, dtype=jnp.float32).sum(1)
            if self.seq_aux_alpha:
                f = chose.reshape(B, L, E).sum(1) * (E / (K * L))
                p = (scores / scores.sum(-1, keepdims=True)).reshape(
                    B, L, E).mean(1)
                self.sow("losses", "moe_seq_aux", self.seq_aux_alpha
                         * jnp.mean(jnp.sum(f * p, -1)))
            # pairs sorted by held expert; those on absent experts last
            local = idx.reshape(-1) - first
            here = (local >= 0) & (local < count)
            key = jnp.where(here, local, count)
            order = jnp.argsort(key, stable=True)
            sizes = jnp.zeros((count + 1,), jnp.int32).at[key].add(1)[:count]
        with scope("moe_experts"):
            stack = _ExpertStack(count, self.width, name="experts")(C)
            routed, rows = grouped_swiglu(
                tokens.astype(self.dtype), (order // K).astype(jnp.int32),
                gates.reshape(-1)[order], sizes, stack["gate_proj"],
                stack["up_proj"], stack["down_proj"],
                min(GMM_CHUNK_ROWS, B * L * K), self.dtype)
        for name, value in (("expert_counts", chose.sum(0)),
                            ("routed_here", here.sum()),
                            ("rows_grouped", rows),
                            ("rows_max", sizes.max())):
            self.sow("counters", name, value)
        if self.router_hidden:
            self.sow("counters", "gate_mean", gates.mean())
            self.sow("counters", "router_entropy", -jnp.mean(jnp.sum(
                scores * jnp.log(scores + 1e-30), -1)))
        out = routed
        if self.n_shared:
            with scope("moe_shared"):
                out = out + _SwiGLU(self.n_shared * self.width, self.dtype,
                                    name="shared")(tokens)
        out = out.reshape(B, L, C).astype(x.dtype)
        return (out, router_state) if self.router_hidden else out

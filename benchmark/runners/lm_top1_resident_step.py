"""Runner `lm_top1_resident_step`: the compiled LM train step of a
configured decoder whose router picks one expert a token and whose
embedding is tied to a large head (``models/decoder.py`` with
``router_hidden_size`` and ``tie_word_embeddings``), on one resident batch
of token sequences.

``lm_resident_step.py`` with two things of its own.  The model, its AdamW,
the step, the state and the batch are that runner's (``build_model``,
``make_step``, ``make_state``, ``make_batch``, loaded from its file: what
``recipes/lm_pretrain --model-config <file>`` hands ``LMTrainer``), and so
is the timing: chunks of about a second, a ``block_until_ready`` at each
chunk's end, the trace after the window.  An item is a token.

- The comparison never holds the logits.  8,192 positions over 131,136 ids
  are 4.3 GB in float32, and that runner's comparison holds them three
  times over beside the train state.  Here both sides hand over the rows
  the head reads, and the reference's ``logits_error`` and ``loss_rows``
  multiply them against the head a block of rows at a time.  Otherwise it
  is that runner's: the timed model at the timed sizes on the first
  sequence of the resident batch, with the weights the run starts from and
  the selection bias drawn non-zero; logits, loss and the gradients of the
  reference's ``GRAD_LEAVES`` (the tied embedding's whole gradient among
  them), over the positions the reference finds clear of routing ties.
- The routing check is the one a top-1 router can hold on every seed.
  With one expert a token and half the experts held, a layer's share of
  tokens on the held experts is anything from none to all, by seed and by
  step (PERF.md 6, PR 32), so ``routed_here > 0`` is no law of the step.
  ``no_token_dropped`` asks that every pair routed to a held expert was
  processed (``rows_grouped == routed_here``) on every step.  The
  comparison's selection bias is drawn, as that runner's is, and also leans
  each layer towards or away from the held experts (``BIAS_LEAN``), so that
  on every seed some layers route nearly every row here and the others
  nearly none; ``comparison_reaches_held_experts`` asks that the last
  layer, whose held experts' gradient is compared, routed rows here.

The cell gates memory, set-up and these checks, and no rate: a step's time
follows the rows routed here.  The rate is computed and printed in the
``window`` line all the same.
"""

from __future__ import annotations

import math
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import harness  # noqa: E402

_LM = harness.load_module(os.path.join(
    harness.HERE, "runners", "lm_resident_step.py"))
build_model, make_step, make_state, make_batch = (
    _LM.build_model, _LM.make_step, _LM.make_state, _LM.make_batch)

# The comparison's selection bias.  A seeded router puts most of a layer's
# tokens on one expert (PERF.md 6, PR 32: the fullest expert has 33-99% of
# them), so under a bias that is only drawn a layer's rows reach the held
# experts all or hardly at all, by seed, and a comparison whose last layer
# routes nothing here compares no expert's products.  So each layer's bias
# leans by BIAS_LEAN, more than a seeded router's probabilities differ (a
# sixteenth is the uniform share; the pick's reads 0.10-0.15), towards the
# held experts in the last layer and every second one before it and away
# from them in the others: layers that route nearly every row here and
# layers that route nearly none, on every seed.  On top a normal draw of
# deviation BIAS_DRAW, the size of the gaps between a router's leading
# probabilities, so that selection (p + b) and gate (p) differ by expert.
BIAS_DRAW = 0.01
BIAS_LEAN = 0.1


def _merge(params, leaves, n_layers: int):
    """``params`` with the reference's ``grad_leaves`` replaced."""
    import jax

    out = jax.tree_util.tree_map(lambda x: x, params)
    for name, leaf in leaves.items():
        *path, last = [f"layer_{n_layers - 1}" if k == "layer_last" else k
                       for k in name.split("/")]
        node = out
        for key in path:
            node = node[key]
        node[last] = leaf
    return out


def comparison(model, cfg, ref, q_block: int, row_block: int):
    """``check(params, theirs, tokens, seed)``: the program under its
    precision policy on the weights ``theirs`` against the plain float32
    reference ``ref`` on ``params``, on ``tokens`` [1, L], as the
    reference's ``agreement``.  The reference runs first and says which
    positions are clear of routing ties (its ``clear_of_ties``); logits are
    compared there, both losses are means over those positions, and the
    gradients are of those objectives.  The program's side is the timed
    step's: the hidden rows against the tied embedding, and the fused loss
    (``ops/fused_ce.py``) in the configuration's chunks.  ``seed`` draws
    the selection bias."""
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_tpu.ops.fused_ce import fused_ce_sums
    from pytorch_distributed_tpu.train.lm import head_matrix

    config = model.config
    n_layers = config.num_hidden_layers
    first, count = config.experts_held
    chunks = cfg["training"]["fused_ce_chunks"]

    def reference(leaves, params, bias, tokens):
        flat = {name: layer["moe"]["e_score_correction_bias"]
                for name, layer in bias.items()}
        merged = _merge(params, leaves, n_layers)
        rows, counts, margin = ref.hidden(
            cfg, merged, flat, tokens, experts_held=config.experts_held,
            q_block=q_block)
        clear = ref.clear_of_ties(margin)
        loss = ref.loss_rows(rows, ref.embedding_of(merged), tokens, clear,
                             row_block)
        held = jnp.stack([c[first:first + count].sum()
                          for c in counts.values()])
        return loss, (rows, clear, held)

    def program(leaves, params, bias, tokens, clear):
        merged = _merge(params, leaves, n_layers)
        hidden, sown = model.apply(
            {"params": merged, model.state_collection: bias}, tokens,
            mutable=["losses", "counters"], return_hidden=True)
        # as make_lm_train_step's loss: the hidden rows against the head
        # in the policy's type, chunk by chunk
        head = head_matrix(model, merged).astype(model.dtype)
        rows = hidden.astype(model.dtype)
        weight = clear[:, :-1].reshape(-1).astype(jnp.float32)
        loss_sum, _ = fused_ce_sums(
            rows[:, :-1].reshape(-1, rows.shape[-1]), head,
            tokens[:, 1:].reshape(-1), weight, chunks)
        loss = loss_sum / jnp.sum(weight)
        return (loss + sum(jax.tree_util.tree_leaves(
            sown.get("losses", {}))), (rows, loss))

    def drawn_bias(seed):
        held = (jnp.arange(config.n_routed_experts) >= first) & (
            jnp.arange(config.n_routed_experts) < first + count)
        layers = range(config.first_k_dense_replace, n_layers)
        keys = jax.random.split(jax.random.PRNGKey(seed), len(layers))
        return {f"layer_{i}": {"moe": {"e_score_correction_bias": (
            BIAS_DRAW * jax.random.normal(key, held.shape)
            + jnp.where(held, BIAS_LEAN, 0.0) * (-1) ** (n_layers - 1 - i))}}
            for i, key in zip(layers, keys)}

    # every array an argument: a closed-over one is a constant of the
    # program, and no cache hit
    def check(params, theirs, tokens, seed):
        bias = drawn_bias(seed)
        with jax.default_matmul_precision("highest"):
            (want_loss, (want_rows, clear, held)), want_grads = (
                jax.value_and_grad(reference, has_aux=True)(
                    ref.grad_leaves(params, n_layers), params, bias, tokens))
        (_, (rows, loss)), grads = jax.value_and_grad(
            program, has_aux=True)(
                ref.grad_leaves(theirs, n_layers), theirs, bias, tokens,
                clear)
        with jax.default_matmul_precision("highest"):
            worst, top = ref.logits_error(
                rows, head_matrix(model, theirs).astype(model.dtype),
                want_rows, ref.embedding_of(params), row_block)
        out = ref.agreement(worst, top, loss, want_loss, grads, want_grads,
                            clear)
        out.update(loss=loss, ref_loss=want_loss, logits_top=top,
                   held_rows_last=held[-1], held_rows_min=jnp.min(held),
                   held_rows_max=jnp.max(held))
        return out

    return check


def reference_check(model, cfg, params, tokens, seed: int, q_block: int,
                    row_block: int, program_params=None, slack: float = 1.0):
    """``comparison`` as one jitted program, cached like the step, and
    whether its measures are within the reference's ``TOLERANCE``.
    ``program_params`` (the weights the program runs on, default
    ``params``) and ``slack`` (the reference's ``within_tolerance``) are
    for the tests and the two readings of PERF.md: the program on 8-bit
    weights must come out as not agreeing."""
    import jax
    import jax.numpy as jnp

    ref = harness.load_module(os.path.join(
        harness.HERE, "reference", cfg["reference"] + ".py"))
    out = {k: float(v) for k, v in jax.jit(
        comparison(model, cfg, ref, q_block, row_block))(
            params, params if program_params is None else program_params,
            tokens, jnp.uint32(seed + 1)).items()}
    out.update(tie_gap=ref.TIE_GAP, tolerance=ref.TOLERANCE)
    out["ok"] = ref.within_tolerance(out, slack)
    return out


def run(cell: harness.Cell) -> harness.Run:
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_tpu.parallel import data_parallel_mesh

    cfg, traffic, spans = cell.config, cell.traffic, cell.spans
    if cfg["training"]["seq_len"] != traffic["seq_len"]:
        raise ValueError("the configuration counts its operations at "
                         f"seq_len {cfg['training']['seq_len']}, the "
                         f"traffic runs {traffic['seq_len']}")
    sequences = traffic["sequences_per_chip"] * cell.chips
    batch_tokens = sequences * traffic["seq_len"]
    mesh = data_parallel_mesh(cell.devices)
    model, tx = build_model(cfg)
    state = make_state(model, tx, mesh, cell.seed)
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(state.params))
    state_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(state))
    harness.say("model", parameters=n_params, state_bytes=state_bytes,
                experts_held=model.config.experts_held,
                tokens_per_step=batch_tokens)
    batch = make_batch(cfg, traffic, mesh, sequences, cell.seed)
    checks = {"batch_on_every_device":
              harness.placed_everywhere(batch, cell.devices)}
    ref = reference_check(model, cfg, state.params, batch[:1], cell.seed,
                          traffic["reference_q_block"],
                          traffic["reference_row_block"],
                          slack=traffic.get("reference_slack", 1.0))
    harness.say("reference", **ref)
    checks["agrees_with_reference"] = ref["ok"]
    checks["comparison_reaches_held_experts"] = ref["held_rows_last"] > 0

    step = make_step(model, mesh, cfg, tx, state.params)
    lr = jnp.float32(cfg["optimizer"]["lr"])
    # warm-up: the first call compiles or loads; the rest size the chunks
    state, metrics = step(state, batch, lr)
    jax.block_until_ready((state, metrics))
    warm = traffic["warmup_steps"]
    t = time.perf_counter()
    for _ in range(warm):
        state, metrics = step(state, batch, lr)
    jax.block_until_ready((state, metrics))  # the drain before the window
    step_s = (time.perf_counter() - t) / warm
    per_chunk = max(1, round(1.0 / step_s))
    harness.say("warm", step_ms=step_s * 1e3, steps_per_chunk=per_chunk)

    seen, attempted = [], 0

    def chunk():
        nonlocal state, metrics, attempted
        with spans("dispatch"):
            for _ in range(per_chunk):
                attempted += 1
                state, metrics = step(state, batch, lr)
                seen.append(metrics)
        with spans("block"):
            jax.block_until_ready((state, metrics))

    chunk_s, failed = [], 0
    t0 = t1 = time.perf_counter()
    while t1 - t0 < cell.seconds:
        try:
            chunk()
        except Exception as e:  # a step that raised: counted, window ends
            failed += 1
            harness.say("step_raised", error=repr(e)[:300])
            break
        now = time.perf_counter()
        chunk_s.append(now - t1)
        t1 = now
    in_window = attempted
    compiler_bytes = None
    # eight traced seconds: a step takes about half of one
    tracer = harness.TraceWindow(cell, seconds=8.0) if cell.trace else None
    if tracer:  # the same loop, after the window, under the profiler
        tracer.start()
        while tracer.open():
            chunk()
        tracer.stop()
        # after everything timed, as in resident_step
        compiled = step.lower(state, batch, lr).compile()
        cost = compiled.cost_analysis()
        compiler_bytes = float(cost["bytes accessed"])
        harness.say("compiler", bytes_accessed=compiler_bytes,
                    flops=cost.get("flops"),
                    memory=str(compiled.memory_analysis()))

    steps = [{k: float(v) for k, v in m.items()} for m in seen[:in_window]]
    values = [s["loss"] for s in steps]
    failed += sum(1 for v in values if not math.isfinite(v))
    done = len(chunk_s) * per_chunk
    tenth = max(1, len(values) // 10)
    checks["losses_finite"] = failed == 0
    checks["loss_falls_on_reused_batch"] = bool(
        values and sum(values[-tenth:]) / tenth < sum(values[:tenth]) / tenth)
    checks["no_compile_in_window"] = cell.compiles.inside(t0, t1) == 0
    # the step's own counters: pairs routed to held experts = rows the
    # grouped products processed, every step, be they all or none
    checks["no_token_dropped"] = bool(steps) and all(
        s["rows_grouped"] == s["routed_here"] for s in steps)
    counters = {name: [s[name] for s in steps] for name in model.counter_names}
    harness.say("losses", first=values[:3], last=values[-3:], n=len(values))
    harness.say("counters", **{name: {
        "first": v[:2], "median": statistics.median(v) if v else None,
        "min": min(v, default=None), "max": max(v, default=None)}
        for name, v in counters.items()})
    harness.say("chunks", seconds=chunk_s, steps_per_chunk=per_chunk)
    return harness.Run(
        items=done * batch_tokens, window_start=t0, window_end=t1,
        attempted=in_window, failed=failed, checks=checks,
        end_to_end={"throughput_per_chip":
                    done * batch_tokens / (t1 - t0) / cell.chips},
        trace_file=tracer.file if tracer else None,
        compiler_bytes=compiler_bytes,
        notes={"batch": batch_tokens, "step_program": "jit_step",
               "state_bytes": state_bytes})

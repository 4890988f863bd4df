"""Runner `lm_resident_step`: the compiled LM train step of a configured
decoder (``models/decoder.py``) on one resident batch of token sequences.

``resident_step.py`` for the language-model trainer.  The model, its AdamW
and the step are built with what ``recipes/lm_pretrain --model-config
<file>`` hands ``LMTrainer`` and ``LMTrainer`` hands ``make_lm_train_step``
(``benchmark/tests`` holds the two to the same lowered program).  The state
is made on the device from the seed in one jitted call; the batch (Zipf
token ids over the vocabulary held, rank -> id by a seeded permutation) is
made on the device too and reused; no loader, no host-to-device copy.
Timing is ``resident_step``'s: chunks of about a second, a
``block_until_ready`` at each chunk's end.  An item is a token.

The cell gates memory, set-up and the checks below, and no rate.  A
step's time follows the rows routed to the held experts (the grouped
products' loops run as many passes as there are pairs), and seeded routers
send there 4 to 26% of all pairs, by seed and by step, so the rate spreads
by a tenth over seeds (my chip runs, PR 26: PERF.md 6).  It is computed and
printed in the ``window`` line all the same.

The comparison with the plain reference (``reference/<name>.py``) runs the
timed model at the timed sizes on the first sequence of the resident batch,
with the weights the run starts from and the selection bias drawn non-zero:
logits, loss and the gradients of the reference's ``GRAD_LEAVES``, the
reference computed in blocks of query rows and one expert at a time.
"""

from __future__ import annotations

import math
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import harness  # noqa: E402

BIAS_DRAW = 0.05  # the comparison's selection bias: normal, this deviation


def build_model(cfg):
    """The model and its optax ``tx`` as ``lm_pretrain --model-config``
    builds them (``--precision`` is the file's): the file's AdamW at its
    rate, constant."""
    import jax.numpy as jnp

    from pytorch_distributed_tpu.models.decoder import DecoderConfig, DecoderLM
    from pytorch_distributed_tpu.recipes.lm_pretrain import decoder_tx

    dtype = {"bf16": jnp.bfloat16, "fp32": jnp.float32}[cfg["precision"]]
    model = DecoderLM(DecoderConfig.from_dict(cfg), dtype=dtype)
    return model, decoder_tx(cfg["optimizer"], cfg["optimizer"]["lr"])


def make_step(model, mesh, cfg, tx, params):
    """``make_lm_train_step`` as ``LMTrainer._build_for_mesh`` calls it for
    ``lm_pretrain --model-config <file>`` with the recipe's other
    defaults: GSPMD, no clipping, no accumulation, no norms, no guard."""
    from pytorch_distributed_tpu.parallel.tp import replicated_like
    from pytorch_distributed_tpu.train.lm import make_lm_train_step

    return make_lm_train_step(
        model, mesh, replicated_like(params), grad_compress="none",
        zero="none", params=params, clip_grad_norm=0.0, accum_steps=1,
        fused_ce_chunks=cfg["training"]["fused_ce_chunks"],
        fused_ce_mode="auto", overlap="none", bucket_mb=4.0, tx=tx,
        log_norms=False, guard_nonfinite=False)


def make_state(model, tx, mesh, seed: int):
    """The train state, made on the device from the seed in one jitted
    call: parameters, the selection bias (``batch_stats``), AdamW's two
    moments (``momentum``)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from pytorch_distributed_tpu.train.state import TrainState

    def init_state(seed):
        variables = model.init(jax.random.PRNGKey(seed),
                               jnp.zeros((1, 16), jnp.int32))
        return TrainState.create(
            {"params": variables["params"],
             "batch_stats": variables[model.state_collection]},
            tx.init(variables["params"]))

    return jax.jit(init_state, out_shardings=NamedSharding(mesh, P()))(
        jnp.uint32(seed))


def make_batch(cfg, traffic, mesh, sequences: int, seed: int):
    """``sequences`` rows of ``seq_len`` token ids on the devices, rows
    sharded over ``data``: Zipf over the ids held, by inverse CDF."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    ids = traffic["token_ids"]
    if ids["distribution"] != "zipf":
        raise ValueError(f"no token distribution {ids['distribution']!r}")
    vocab, seq = cfg["vocab_size"], traffic["seq_len"]

    def draw(seed):
        k_rank, k_perm = jax.random.split(jax.random.PRNGKey(seed))
        weight = jnp.arange(1, vocab + 1, dtype=jnp.float32) ** -float(
            ids["exponent"])
        cdf = jnp.cumsum(weight) / jnp.sum(weight)
        rank = jnp.searchsorted(
            cdf, jax.random.uniform(k_rank, (sequences, seq)))
        return jax.random.permutation(k_perm, vocab)[
            jnp.minimum(rank, vocab - 1)].astype(jnp.int32)

    return jax.jit(draw, out_shardings=NamedSharding(
        mesh, P("data", None)))(jnp.uint32(seed))


def comparison(model, cfg, ref, q_block: int):
    """``check(params, theirs, tokens, seed)``: the program under its
    precision policy on the weights ``theirs`` against the plain float32
    reference ``ref`` on ``params``, on ``tokens`` [1, L], as the
    reference's ``agreement``.  The reference runs first and says which
    positions are clear of routing ties (its ``clear_of_ties``); logits are
    compared there, both losses are means over those positions, and the
    gradients are of those objectives.  The program's side is the timed step's: the hidden rows
    against the head, and the fused loss (``ops/fused_ce.py``) in the
    configuration's chunks.  ``seed`` draws the selection bias."""
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_tpu.ops.fused_ce import fused_ce_sums
    from pytorch_distributed_tpu.train.lm import head_matrix

    config = model.config
    n_layers = config.num_hidden_layers
    chunks = cfg["training"]["fused_ce_chunks"]

    def reference(leaves, params, bias, tokens):
        flat = {name: layer["moe"]["e_score_correction_bias"]
                for name, layer in bias.items()}
        logits, aux, _, margin = ref.forward(
            cfg, _merge(params, leaves), flat, tokens,
            experts_held=config.experts_held, q_block=q_block)
        clear = ref.clear_of_ties(margin)
        loss = ref.loss(logits, tokens, clear)
        return loss + aux, (logits, loss, clear)

    def program(leaves, params, bias, tokens, clear):
        merged = _merge(params, leaves)
        hidden, sown = model.apply(
            {"params": merged, model.state_collection: bias}, tokens,
            mutable=["losses", "counters"], return_hidden=True)
        # as make_lm_train_step's loss: the hidden rows against the head
        # in the policy's type, chunk by chunk
        head = head_matrix(model, merged).astype(model.dtype)
        rows = hidden.astype(model.dtype)
        logits = jnp.einsum("bld,vd->blv", rows, head,
                            preferred_element_type=jnp.float32)
        weight = clear[:, :-1].reshape(-1).astype(jnp.float32)
        loss_sum, _ = fused_ce_sums(
            rows[:, :-1].reshape(-1, rows.shape[-1]), head,
            tokens[:, 1:].reshape(-1), weight, chunks)
        loss = loss_sum / jnp.sum(weight)
        return (loss + sum(jax.tree_util.tree_leaves(sown["losses"])),
                (logits, loss))

    bias_shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 16), jnp.int32)))[
                               model.state_collection]

    # every array an argument: a closed-over one is a constant of the
    # program, and no cache hit
    def check(params, theirs, tokens, seed):
        keys = jax.random.split(jax.random.PRNGKey(seed), len(
            jax.tree_util.tree_leaves(bias_shapes)))
        bias = jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(bias_shapes),
            [BIAS_DRAW * jax.random.normal(k, b.shape)
             for k, b in zip(keys, jax.tree_util.tree_leaves(bias_shapes))])
        with jax.default_matmul_precision("highest"):
            (_, (want, want_loss, clear)), want_grads = jax.value_and_grad(
                reference, has_aux=True)(
                    ref.grad_leaves(params, n_layers), params, bias, tokens)
        (_, (logits, loss)), grads = jax.value_and_grad(
            program, has_aux=True)(
                ref.grad_leaves(theirs, n_layers), theirs, bias, tokens,
                clear)
        out = ref.agreement(logits, want, loss, want_loss, grads, want_grads,
                            clear)
        out.update(loss=loss, ref_loss=want_loss,
                   logits_top=jnp.max(jnp.abs(want)))
        return out

    def _merge(params, leaves):
        out = jax.tree_util.tree_map(lambda x: x, params)
        for name, leaf in leaves.items():
            node = out
            *path, last = [f"layer_{n_layers - 1}" if k == "layer_last"
                           else k for k in name.split("/")]
            for key in path:
                node = node[key]
            node[last] = leaf
        return out

    return check


def reference_check(model, cfg, params, tokens, seed: int, q_block: int,
                    program_params=None, slack: float = 1.0):
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
        comparison(model, cfg, ref, q_block))(
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
                          slack=traffic.get("reference_slack", 1.0))
    harness.say("reference", **ref)
    checks["agrees_with_reference"] = ref["ok"]

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
    # eight traced seconds: a step takes about one
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
    # grouped products processed, every step
    checks["no_token_dropped"] = bool(steps) and all(
        s["rows_grouped"] == s["routed_here"] > 0 for s in steps)
    counters = {name: [s[name] for s in steps] for name in model.counter_names}
    harness.say("losses", first=values[:3], last=values[-3:], n=len(values))
    harness.say("counters", **{name: {
        "first": v[:2], "median": statistics.median(v) if v else None}
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

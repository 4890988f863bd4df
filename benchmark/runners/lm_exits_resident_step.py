"""Runner `lm_exits_resident_step`: the compiled LM train step of a looped,
multi-exit decoder (``models/decoder.py`` with ``total_ut_steps`` > 1) on
one resident batch of token sequences.

``lm_resident_step.py`` for a model whose step time does not follow its
data: nothing in a dense step depends on the tokens, so this cell's rate is
gated.  The model, its AdamW, the step and the batch are that runner's own
(``build_model``, ``make_step``, ``make_batch``, loaded from its file: what
``recipes/lm_pretrain --model-config <file>`` hands ``LMTrainer``); the
state is made here, since this model has no non-gradient state to put
beside its parameters.  Timing is ``resident_step``'s: chunks of whole
steps, a ``block_until_ready`` at each chunk's end, the trace after the
window.  An item is a token.

The comparison with the plain reference (``reference/<name>.py``) holds the
timed step itself to it: the compiled step's first call, on the whole
resident batch with the weights the run starts from, returns its loss and
each exit's own cross-entropy, leaves AdamW's first moment (a tenth of the
gradient it took) and changes the weights; all four are compared, the last
two on the reference's ``GRAD_LEAVES``, with the reference's objective and
gradients over every sequence of the batch and a plain AdamW.  The step
returns no value a position, so every exit's logits (in blocks of rows:
four exits' float32 logits over the whole vocabulary do not fit twice
beside the state) and the exit distribution are those of the timed model's
forward pass on the batch's first sequence.
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
build_model, make_step, make_batch = (
    _LM.build_model, _LM.make_step, _LM.make_batch)


def make_state(model, tx, mesh, seed: int):
    """The train state, made on the device from the seed in one jitted
    call: parameters and AdamW's two moments (``momentum``)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from pytorch_distributed_tpu.train.state import TrainState

    def init_state(seed):
        params = model.init(jax.random.PRNGKey(seed),
                            jnp.zeros((1, 16), jnp.int32))["params"]
        return TrainState.create({"params": params}, tx.init(params))

    return jax.jit(init_state, out_shardings=NamedSharding(mesh, P()))(
        jnp.uint32(seed))


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
    """Two functions to jit.  ``reference(params, tokens)``: the plain
    float32 reference ``ref`` on ``tokens`` [B, L], one sequence after
    another: the objective, each exit's mean cross-entropy and the gradients
    of its ``grad_leaves`` over all of them, and the first sequence's hidden
    rows of every exit and exit distribution.  ``forward(params, tokens,
    rows, head)``: the model under its precision policy on the first
    sequence against those rows and the reference's head: the reference's
    ``logits_error``, and the exit distribution as the step's loss reads
    it."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from pytorch_distributed_tpu.train.lm import head_matrix

    n_layers = model.config.num_hidden_layers
    beta = cfg["training"]["exit_entropy_beta"]

    def reference(params, tokens):
        def sequence(row):
            def objective(leaves):
                return ref.objective(
                    cfg, _merge(params, leaves, n_layers), row[None],
                    beta=beta, q_block=q_block, row_block=row_block)

            (loss, (rows, p, exit_ce)), grads = jax.value_and_grad(
                objective, has_aux=True)(ref.grad_leaves(params, n_layers))
            sums = {"loss": loss, "exit_ce": exit_ce, "grads": grads}
            return sums, (rows, p)

        with jax.default_matmul_precision("highest"):
            sums, (rows, p) = lax.map(sequence, tokens)
        # sequences of one length: the batch's mean is the mean of theirs
        want = jax.tree_util.tree_map(lambda x: jnp.mean(x, 0), sums)
        return {**want, "rows": rows[0], "p": p[0]}

    def forward(params, tokens, want_rows, want_head):
        rows, sown = model.apply(
            {"params": params}, tokens, return_hidden=True,
            mutable=["losses", "counters", "exits"])
        # as make_lm_train_step's loss multiplies them: the exits' rows
        # against the head, both in the policy's type
        with jax.default_matmul_precision("highest"):
            worst, top = ref.logits_error(
                rows.astype(model.dtype),
                head_matrix(model, params).astype(model.dtype),
                want_rows, want_head, row_block)
        return worst, top, sown["exits"]["weight"][0]

    return reference, forward


def _reference_module(cfg):
    return harness.load_module(os.path.join(
        harness.HERE, "reference", cfg["reference"] + ".py"))


def reference_check(model, cfg, state, batch, step, q_block: int,
                    row_block: int, slack: float = 1.0, coarse=None):
    """``step``'s first call on ``state`` and ``batch`` against the
    reference, and the model's forward pass on ``batch[:1]`` (the
    ``comparison``'s two programs): the state after that call, and the
    reference's measures with whether they are within its ``TOLERANCE`` and
    ``STEP_TOLERANCE``.  ``step`` donates ``state``.  ``coarse`` (a
    function of the parameters) and ``slack`` are for the tests and the two
    readings of PERF.md: the reference reads the weights as they are, the
    program what ``coarse`` makes of them, and on 8-bit weights it must
    come out as not agreeing."""
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_tpu.train.lm import head_matrix

    ref = _reference_module(cfg)
    opt, n_layers = cfg["optimizer"], model.config.num_hidden_layers
    reference, forward = comparison(model, cfg, ref, q_block, row_block)
    want = jax.jit(reference)(state.params, batch)
    head = head_matrix(model, state.params)
    if coarse is not None:
        head, fine = jnp.copy(head), state.params
        state = state.replace(params=coarse(fine))
        # the caller's ``state`` is the step's to donate: its weights go
        # now, so that the step finds the room it has in a run
        jax.tree_util.tree_map(lambda x: x.delete(), fine)
    worst, top, p = jax.jit(forward)(
        state.params, batch[:1], want.pop("rows"), head)
    del head
    before = jax.tree_util.tree_map(
        jnp.copy, ref.grad_leaves(state.params, n_layers))
    state, metrics = step(state, batch, jnp.float32(opt["lr"]))

    @jax.jit
    def measure(worst, top, p, want, metrics, momentum, before, after):
        # AdamW's first moment after one step from zero: (1 - b1) g
        moments = next(s.mu for s in momentum if hasattr(s, "mu"))
        grads = {name: mu / (1.0 - opt["b1"])
                 for name, mu in ref.grad_leaves(moments, n_layers).items()}
        out = ref.agreement(worst, top, p, want["p"], metrics["loss"],
                            want["loss"], grads, want["grads"])
        out.update(ref.step_agreement(
            jnp.stack([metrics[f"loss_exit_{t + 1}"]
                       for t in range(model.n_exits)]),
            want["exit_ce"], grads, before,
            ref.grad_leaves(after, n_layers), opt))
        return out

    measures = measure(worst, top, p, want, metrics, state.momentum, before,
                       state.params)
    out = {k: float(v) for k, v in measures.items()}
    out.update(loss=float(metrics["loss"]), ref_loss=float(want["loss"]),
               logits_top=float(top),
               **{f"ref_loss_exit_{t + 1}": float(v)
                  for t, v in enumerate(want["exit_ce"])})
    limits = {**ref.TOLERANCE, **ref.STEP_TOLERANCE}
    out["tolerance"] = limits
    out["ok"] = ref.within_tolerance(out, slack, limits)
    return state, out


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
                passes=model.config.total_ut_steps,
                layers=model.config.num_hidden_layers,
                tokens_per_step=batch_tokens)
    batch = make_batch(cfg, traffic, mesh, sequences, cell.seed)
    checks = {"batch_on_every_device":
              harness.placed_everywhere(batch, cell.devices)}
    step = make_step(model, mesh, cfg, tx, state.params)
    lr = jnp.float32(cfg["optimizer"]["lr"])
    # the step's first call (it compiles or loads) is the one compared
    state, agreed = reference_check(
        model, cfg, state, batch, step, traffic["reference_q_block"],
        traffic["reference_row_block"],
        slack=traffic.get("reference_slack", 1.0))
    harness.say("reference", **agreed)
    checks["agrees_with_reference"] = agreed["ok"]
    # warm-up: the steps that size the chunks
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
    # ten traced seconds: a step takes about three
    tracer = harness.TraceWindow(cell, seconds=10.0) if cell.trace else None
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

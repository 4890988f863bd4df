#!/usr/bin/env python
"""KV-cached autoregressive decode throughput on the real chip.

The serving-side benchmark the training results don't cover: prefill
latency and steady-state decode tokens/s for the TransformerLM generate
path (``models/generate.py`` — one compiled program: prefill + lax.scan
over decode steps, cached across calls).

Decode at small batch is memory-bandwidth-bound: every generated token
re-reads the full parameter set (bf16: 2·N_params bytes) plus the growing
KV cache, so the per-token floor is  bytes_read / HBM_BW.  We report that
roofline next to the measurement, per batch size — batch amortizes the
parameter stream, which is the whole serving-throughput story.

Methodology: time generate() at max_new_tokens=1 (prefill + first token)
and at max_new_tokens=N; the difference isolates N-1 steady-state decode
steps.  Reference analogue: the reference's inference story is
``--evaluate`` (distributed.py:197-199); generation is the LM-family
counterpart built on the same harness.

Run on the TPU chip:
    PYTHONPATH=/root/repo python experiments/decode_bench.py
"""

from __future__ import annotations

import json
import os
import time

import numpy as np

D_MODEL = int(os.environ.get("DECODE_BENCH_D", "1024"))
N_LAYERS = int(os.environ.get("DECODE_BENCH_LAYERS", "12"))
N_HEADS = int(os.environ.get("DECODE_BENCH_HEADS", "16"))
VOCAB = int(os.environ.get("DECODE_BENCH_VOCAB", "32000"))
PROMPT = int(os.environ.get("DECODE_BENCH_PROMPT", "512"))
NEW = int(os.environ.get("DECODE_BENCH_NEW", "257"))
REPS = int(os.environ.get("DECODE_BENCH_REPS", "3"))
HBM_GBPS = float(os.environ.get("DECODE_BENCH_HBM_GBPS", "819"))  # v5e


def _time(fn, reps: int) -> float:
    # Sync: reduce the tokens to a scalar and fetch its value — the fetch
    # waits for the device.
    int(fn().sum())  # compile + warm
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        int(fn().sum())
        best = min(best, time.perf_counter() - t0)
    return best


def main() -> int:
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_tpu.models.generate import generate
    from pytorch_distributed_tpu.models.transformer import TransformerLM

    cfg = dict(vocab_size=VOCAB, d_model=D_MODEL, n_heads=N_HEADS,
               n_layers=N_LAYERS)
    model = TransformerLM(**cfg, dtype=jnp.bfloat16)
    rng = np.random.default_rng(0)
    init_tokens = jnp.asarray(
        rng.integers(0, VOCAB, size=(1, 16)).astype(np.int32))
    params = model.init(jax.random.PRNGKey(0), init_tokens)["params"]
    params = jax.device_put(params)
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    param_bytes = 2 * n_params  # decode streams the bf16 copy

    from pytorch_distributed_tpu.models.quant import quantize_lm_params

    qparams = jax.device_put(quantize_lm_params(params))
    # Streamed bytes: int8 kernels as-is; every fp leaf streams as the
    # bf16 compute copy (the f32->bf16 cast is hoisted out of the scan).
    q_bytes = sum(
        x.size * (1 if x.dtype == jnp.int8 else 2)
        for x in jax.tree_util.tree_leaves(qparams))

    here = os.path.dirname(os.path.abspath(__file__))
    out_path = os.path.join(here, "..", "RESULTS_decode.json")
    # Resumable per-row writes (arch_bench pattern): the watcher runs this
    # under a timeout with a capped retry budget — completed rows must
    # survive a killed sweep or retries redo everything and land nothing.
    results = {}
    if os.path.exists(out_path):
        try:
            with open(out_path) as f:
                prior = json.load(f)
            pm = prior.get("meta", {})
            if (pm.get("d_model") == D_MODEL and pm.get("vocab") == VOCAB
                    and pm.get("n_layers") == N_LAYERS
                    and pm.get("n_heads") == N_HEADS
                    and pm.get("prompt") == PROMPT
                    and pm.get("platform") == jax.default_backend()):
                results = prior.get("configs", {})
        except ValueError:
            pass

    def write():
        out = {
            "meta": {
                "d_model": D_MODEL, "n_layers": N_LAYERS,
                "n_heads": N_HEADS, "vocab": VOCAB, "prompt": PROMPT,
                "new_tokens": NEW,
                "params_m": round(n_params / 1e6, 1),
                "hbm_gbps_assumed": HBM_GBPS,
                "platform": jax.default_backend(),
                "what": "KV-cached generate(): prefill latency + "
                        "steady-state decode tok/s vs the params+KV "
                        "HBM-stream floor",
                "topk_nucleus_note": "top-k+top-p samples from the sorted "
                        "k-vector (no full-vocab argsort in the scan): "
                        "6.696 -> 1.761 ms/tok measured at b8/vocab 32k",
            },
            "configs": results,
        }
        with open(out_path, "w") as f:
            json.dump(out, f, indent=1)
            f.write("\n")

    for batch, sampling, quant in (
            (1, "greedy", ""), (8, "greedy", ""), (32, "greedy", ""),
            (8, "topk50_topp0.9", ""),
            (1, "greedy", "int8"), (8, "greedy", "int8")):
        prompt = jnp.asarray(
            rng.integers(0, VOCAB, size=(batch, PROMPT)).astype(np.int32))
        kw = dict(cfg, dtype=jnp.bfloat16, quant=quant)
        if sampling != "greedy":
            kw.update(temperature=1.0, top_k=50, top_p=0.9)
        tag = f"b{batch}_p{PROMPT}_{sampling}" + ("_int8w" if quant else "")
        if tag in results:
            print(f"{tag}: cached", flush=True)
            continue
        p = qparams if quant else params
        try:
            t1 = _time(lambda: generate(p, prompt, 1, **kw), REPS)
            tn = _time(lambda: generate(p, prompt, NEW, **kw), REPS)
        except Exception as e:  # noqa: BLE001 — record per-config OOM/abort
            print(f"{tag}: FAILED {repr(e)[:200]}", flush=True)
            continue
        per_tok = (tn - t1) / max(NEW - 1, 1)
        toks_per_s = batch / per_tok
        # Per-step HBM floor: the streamed parameter bytes (bf16, or the
        # int8 tree's actual footprint) + the mean-filled KV cache (k and
        # v, bf16) for every sequence in the batch.
        mean_ctx = PROMPT + NEW / 2
        kv_bytes = 2 * N_LAYERS * batch * mean_ctx * D_MODEL * 2
        stream_bytes = q_bytes if quant else param_bytes
        floor_s = (stream_bytes + kv_bytes) / (HBM_GBPS * 1e9)
        results[tag] = {
            "prefill_plus_1tok_ms": round(t1 * 1e3, 2),
            "per_token_ms": round(per_tok * 1e3, 3),
            "decode_tokens_per_sec": round(toks_per_s, 0),
            "hbm_floor_ms": round(floor_s * 1e3, 3),
            "pct_of_bw_roofline": round(100 * floor_s / per_tok, 1),
        }
        write()
        print(f"{tag}: prefill+1 {t1*1e3:.1f} ms  decode "
              f"{per_tok*1e3:.3f} ms/tok  {toks_per_s:,.0f} tok/s  "
              f"({results[tag]['pct_of_bw_roofline']}% of HBM roofline)",
              flush=True)

    # --- b32 roofline-gap breakdown (VERDICT r4 weak 6): where do the
    # extra ms/tok go at batch 32?  Decompose by re-measuring b32 with a
    # tiny KV cache (prompt 64): params stream is batch-invariant, so
    #   per_tok(b32, p512) - per_tok(b32, p64)  ~= attention-over-cache +
    # KV stream for the extra context, and per_tok(b32, p64) ~= params
    # stream + batched-MLP compute + dispatch.  b1@p64 pins the dispatch+
    # params floor.
    b32_tag = f"b32_p{PROMPT}_greedy"
    if b32_tag in results and "b32_breakdown" not in results:
        try:
            gap = {}
            for b in (1, 32):
                pshort = jnp.asarray(
                    rng.integers(0, VOCAB, size=(b, 64)).astype(np.int32))
                kw = dict(cfg, dtype=jnp.bfloat16)
                t1s = _time(lambda: generate(params, pshort, 1, **kw), REPS)
                tns = _time(lambda: generate(params, pshort, NEW, **kw),
                            REPS)
                gap[f"b{b}_p64_per_token_ms"] = round(
                    (tns - t1s) / max(NEW - 1, 1) * 1e3, 3)
            long_ms = results[b32_tag]["per_token_ms"]
            short_ms = gap["b32_p64_per_token_ms"]
            results["b32_breakdown"] = {
                **gap,
                f"b32_p{PROMPT}_per_token_ms": long_ms,
                "attn_over_cache_ms": round(long_ms - short_ms, 3),
                "note": "per_tok(b32,p512)-per_tok(b32,p64) isolates "
                        "attention-over-cache + long-context KV stream; "
                        "b1_p64 is the params+dispatch floor",
            }
            write()
            print(f"b32 breakdown: {results['b32_breakdown']}", flush=True)
        except Exception as e:  # noqa: BLE001
            print(f"b32_breakdown: FAILED {repr(e)[:200]}", flush=True)

    # --- long-prompt flash prefill (VERDICT r4: parity-tested, never
    # timed).  P=4096: the dense prefill materializes the O(P·max_len)
    # score tensor; the Pallas kernel streams it.  Rows record prefill+1
    # latency for both paths at b1 (dense may OOM — that row then records
    # the failure, which is itself the result).
    long_p = int(os.environ.get("DECODE_BENCH_LONG_PROMPT", "4096"))
    lp_prompt = jnp.asarray(
        rng.integers(0, VOCAB, size=(1, long_p)).astype(np.int32))
    for fp in (False, True):
        tag = f"b1_p{long_p}_prefill_{'flash' if fp else 'dense'}"
        if tag in results:
            print(f"{tag}: cached", flush=True)
            continue
        try:
            kw = dict(cfg, dtype=jnp.bfloat16, flash_prefill=fp)
            t1 = _time(lambda: generate(params, lp_prompt, 1, **kw), REPS)
        except Exception as e:  # noqa: BLE001
            print(f"{tag}: FAILED {repr(e)[:200]}", flush=True)
            results[tag] = {"failed": repr(e)[:200]}
            write()
            continue
        results[tag] = {"prefill_plus_1tok_ms": round(t1 * 1e3, 2)}
        write()
        print(f"{tag}: prefill+1 {t1*1e3:.1f} ms", flush=True)

    # --- speculative decoding (models/speculative.py): draft proposes
    # gamma tokens, target scores the block in ONE cached pass.  On
    # random-init weights the measured acceptance is the FLOOR (a trained
    # draft tracks its target far better), so alongside the end-to-end
    # rows we record the component times (draft ms/step, target ms/pass)
    # and project tok/s at trained-draft acceptance rates from the
    # rejection-sampling algebra: E[tokens/round] = (1-a^(g+1))/(1-a),
    # round cost = g*t_draft + t_target.
    from pytorch_distributed_tpu.models.speculative import (
        speculative_generate,
    )

    draft_cfg = dict(vocab_size=VOCAB, d_model=D_MODEL // 4,
                     n_heads=max(1, N_HEADS // 4),
                     n_layers=max(1, N_LAYERS // 4))
    draft_model = TransformerLM(**draft_cfg, dtype=jnp.bfloat16)
    draft_params = jax.device_put(draft_model.init(
        jax.random.PRNGKey(1), init_tokens)["params"])
    spec_prompt = jnp.asarray(
        rng.integers(0, VOCAB, size=(1, PROMPT)).astype(np.int32))
    gamma = int(os.environ.get("DECODE_BENCH_GAMMA", "4"))
    spec_new = int(os.environ.get("DECODE_BENCH_SPEC_NEW", "129"))
    for tag, temp in (("b1_spec_greedy", 0.0), ("b1_spec_t1.0", 1.0)):
        if tag in results:
            print(f"{tag}: cached", flush=True)
            continue
        try:
            kw = dict(target_cfg=cfg, draft_cfg=draft_cfg, gamma=gamma,
                      dtype=jnp.bfloat16, temperature=temp, seed=0)
            # Warm at the SAME max_new_tokens: max_len keys the compiled
            # cache shapes, so a shorter warm call would leave the timed
            # run recompiling all four block programs.
            speculative_generate(
                params, draft_params, spec_prompt, spec_new, **kw)
            # Best-of-REPS like every other row (_time discipline — a
            # single post-warmup sample is noise-prone); the seeded host
            # RNG makes each repeat replay the
            # identical draft/accept trace, so stats are rep-invariant
            # and the min is a valid latency estimator.
            dt = float("inf")
            for _ in range(max(REPS, 1)):
                t0 = time.perf_counter()
                toks, stats = speculative_generate(
                    params, draft_params, spec_prompt, spec_new, **kw)
                int(toks.sum())  # value fetch = reliable queue barrier
                dt = min(dt, time.perf_counter() - t0)
        except Exception as e:  # noqa: BLE001
            print(f"{tag}: FAILED {repr(e)[:200]}", flush=True)
            continue
        results[tag] = {
            "gamma": gamma,
            "end_to_end_tok_s": round(stats["tokens"] / dt, 1),
            "mean_accepted": round(stats["mean_accepted"], 3),
            "tokens_per_target_pass":
                round(stats["tokens_per_target_pass"], 3),
            "target_passes": stats["target_passes"],
            "note": "random-init draft = acceptance FLOOR; see "
                    "spec_projection for trained-draft projections",
        }
        write()
        print(f"{tag}: {results[tag]['end_to_end_tok_s']} tok/s  "
              f"accepted {stats['mean_accepted']:.2f}/{gamma}  "
              f"{stats['tokens_per_target_pass']:.2f} tok/target-pass",
              flush=True)

    # Component times for the projection: one draft step (L=1) and one
    # target scoring pass (L=gamma+1), both cached-model applies.
    if "spec_projection" in results:
        print("spec_projection: cached", flush=True)
        write()
        print("wrote RESULTS_decode.json", flush=True)
        return 0
    try:
        from pytorch_distributed_tpu.models.speculative import (
            _make_block_apply,
        )

        max_len = PROMPT + spec_new + gamma + 1

        def _component_ms(c, L, p):
            fresh, apply = _make_block_apply(
                L, 1, max_len, c["vocab_size"], c["d_model"], c["n_heads"],
                c["n_layers"], "bfloat16", "")
            cache = fresh()
            toks = jnp.zeros((1, L), jnp.int32)
            _, cache = apply(p, cache, toks)  # compile
            jax.block_until_ready(cache)
            best = float("inf")
            for _ in range(max(REPS, 3)):
                t0 = time.perf_counter()
                lg, c2 = apply(p, cache, toks)
                float(jnp.sum(lg))
                best = min(best, time.perf_counter() - t0)
            return best * 1e3

        t_draft = _component_ms(draft_cfg, 1, draft_params)
        t_target = _component_ms(cfg, gamma + 1, params)
        base_tok_ms = results.get(
            f"b1_p{PROMPT}_greedy", {}).get("per_token_ms")
        proj = {}
        for a in (0.5, 0.7, 0.9):
            exp_toks = (1 - a ** (gamma + 1)) / (1 - a)
            round_ms = gamma * t_draft + t_target
            proj[f"accept_{a}"] = {
                "tokens_per_round": round(exp_toks, 2),
                "proj_tok_s": round(1e3 * exp_toks / round_ms, 1),
            }
        results["spec_projection"] = {
            "draft_step_ms": round(t_draft, 3),
            "target_scorepass_ms": round(t_target, 3),
            "target_only_per_token_ms": base_tok_ms,
            "gamma": gamma,
            "projections": proj,
            "note": "proj_tok_s = E[toks/round]/(gamma*t_draft+t_target); "
                    "host-loop dispatch excluded (measured rows include it)",
        }
        print(f"spec components: draft {t_draft:.2f} ms/step, target "
              f"score {t_target:.2f} ms/pass; projections {proj}",
              flush=True)
    except Exception as e:  # noqa: BLE001
        print(f"spec_projection: FAILED {repr(e)[:200]}", flush=True)

    write()
    print("wrote RESULTS_decode.json", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

#!/usr/bin/env python
"""Compiled-peak memory of the LM train step: fused vs unfused loss head,
single-chip AND 8-way data-sharded.

The fused tied-head+CE (ops/fused_ce.py) exists to keep the [B·L, vocab]
logits tensor out of HBM.  The throughput half of that claim needs the
chip (lm_bench fused rows); the MEMORY half is a
compile-time fact XLA will state on any backend: lower + compile the full
train step (fwd+bwd+SGD) both ways and read ``memory_analysis()`` peak
temp bytes — the same compiled-peak methodology as experiments/pp_memory.py
(RESULTS_pp_memory.json).

Round 5 measured the catch: on an 8-way data-sharded mesh the replicated
variant is net-neutral, because its backward carries a fully replicated
[V, D] f32 dE accumulator while the logits it eliminates were already
batch-sharded.  This run therefore A/Bs THREE loss heads on the 8-way mesh
(same per-device batch as the single-chip row): unfused, fused with the
replicated accumulator (the round-5 regression), and fused in DP mode
(ops/fused_ce.py fused_ce_sums_dp — vocab-row-sharded [V/8, D] dE carry,
per-block all_to_all, cotangent left sharded for the existing GSPMD
gradient reduction).

Writes ``RESULTS_fused_ce_memory.json``.  CPU-safe (compile only):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=/root/repo python experiments/fused_ce_memory.py
"""

from __future__ import annotations

import json
import os
import sys

import jax
import numpy as np

if "xla_force_host_platform_device_count" in os.environ.get("XLA_FLAGS", ""):
    try:
        jax.config.update("jax_platforms", "cpu")
    except RuntimeError:
        pass

D_MODEL = int(os.environ.get("FCM_D", "1024"))
N_LAYERS = int(os.environ.get("FCM_LAYERS", "12"))
N_HEADS = int(os.environ.get("FCM_HEADS", "16"))
VOCAB = int(os.environ.get("FCM_VOCAB", "32000"))
SEQ = int(os.environ.get("FCM_SEQ", "1024"))
BATCH = int(os.environ.get("FCM_BATCH", "4"))  # single-chip row
CHUNKS = int(os.environ.get("FCM_CHUNKS", "8"))
DP = int(os.environ.get("FCM_DP", "8"))  # sharded-mesh width
# Sharded-mesh global batch: same per-device batch as the single-chip row,
# so the two tables answer the same question (per-device loss-head temps).
BATCH_DP = int(os.environ.get("FCM_BATCH_DP", str(BATCH * DP)))


def peak_bytes(fused_ce: int, n_dev: int = 1, batch: int = BATCH,
               mode: str = "auto") -> dict:
    import jax.numpy as jnp

    from pytorch_distributed_tpu.models.transformer import TransformerLM
    from pytorch_distributed_tpu.parallel import MeshSpec, build_mesh
    from pytorch_distributed_tpu.parallel.tp import replicated_like
    from pytorch_distributed_tpu.train.lm import make_lm_train_step
    from pytorch_distributed_tpu.train.optim import sgd_init
    from pytorch_distributed_tpu.train.state import TrainState

    mesh = build_mesh(MeshSpec(("data",), (n_dev,)), jax.devices()[:n_dev])
    model = TransformerLM(
        vocab_size=VOCAB, d_model=D_MODEL, n_heads=N_HEADS,
        n_layers=N_LAYERS, dtype=jnp.bfloat16, attn_impl="dense",
    )
    toks = jnp.zeros((batch, SEQ), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), toks[:1, :8])["params"]
    state = TrainState.create({"params": params}, sgd_init(params))
    step = make_lm_train_step(model, mesh, replicated_like(params),
                              fused_ce_chunks=fused_ce, fused_ce_mode=mode)
    compiled = step.lower(state, toks, jnp.float32(1e-3)).compile()
    m = compiled.memory_analysis()
    return {
        "temp_bytes_mib": round(m.temp_size_in_bytes / 2**20, 1),
        "peak_mib": round(
            (m.temp_size_in_bytes + m.argument_size_in_bytes
             + m.output_size_in_bytes) / 2**20, 1),
    }


def main() -> int:
    logits_mib = BATCH * (SEQ - 1) * VOCAB * 4 / 2**20
    rows = {}
    for tag, chunks, mode in (("unfused", 0, "auto"),
                              (f"fused_c{CHUNKS}", CHUNKS, "replicated")):
        rows[tag] = peak_bytes(chunks)
        print(f"{tag}: temp {rows[tag]['temp_bytes_mib']} MiB "
              f"(peak {rows[tag]['peak_mib']} MiB)", flush=True)
    saved = (rows["unfused"]["temp_bytes_mib"]
             - rows[f"fused_c{CHUNKS}"]["temp_bytes_mib"])

    # --- 8-way data-sharded A/B (per-device batch held at BATCH) ---
    rows_dp = {}
    if len(jax.devices()) >= DP:
        for tag, chunks, mode in (
                ("unfused", 0, "auto"),
                (f"fused_c{CHUNKS}_replicated", CHUNKS, "replicated"),
                (f"fused_c{CHUNKS}_dp", CHUNKS, "dp")):
            rows_dp[tag] = peak_bytes(chunks, n_dev=DP, batch=BATCH_DP,
                                      mode=mode)
            print(f"dp{DP} {tag}: temp {rows_dp[tag]['temp_bytes_mib']} MiB "
                  f"(peak {rows_dp[tag]['peak_mib']} MiB)", flush=True)
    else:
        print(f"SKIP dp{DP} table: only {len(jax.devices())} devices "
              f"(need XLA_FLAGS=--xla_force_host_platform_device_count="
              f"{DP})", flush=True)

    out = {
        "meta": {
            "d_model": D_MODEL, "n_layers": N_LAYERS, "n_heads": N_HEADS,
            "vocab": VOCAB, "seq": SEQ, "batch": BATCH, "chunks": CHUNKS,
            "dp": DP, "batch_dp": BATCH_DP,
            "platform": jax.default_backend(),
            "analytic_logits_f32_mib": round(logits_mib, 1),
            "what": "XLA compiled-peak temp buffers of the full LM train "
                    "step (fwd+bwd+SGD, bf16, dense attn), unfused logits "
                    "head vs fused tied-head+CE (ops/fused_ce.py) — the "
                    "pp_memory.py compiled-peak methodology.  rows = one "
                    "chip; rows_dp = 8-way data-sharded mesh at the same "
                    "per-device batch, A/B-ing the replicated-dE fused "
                    "variant (round-5: net-neutral) against DP mode "
                    "(vocab-row-sharded [V/8, D] dE accumulator, "
                    "fused_ce_sums_dp)",
        },
        "rows": rows,
        "temp_saved_mib": round(saved, 1),
    }
    if rows_dp:
        saved_rep = (rows_dp["unfused"]["temp_bytes_mib"]
                     - rows_dp[f"fused_c{CHUNKS}_replicated"]["temp_bytes_mib"])
        saved_dp = (rows_dp["unfused"]["temp_bytes_mib"]
                    - rows_dp[f"fused_c{CHUNKS}_dp"]["temp_bytes_mib"])
        out["rows_dp"] = rows_dp
        out["dp_temp_saved_mib_replicated_accumulator"] = round(saved_rep, 1)
        out["dp_temp_saved_mib_dp_mode"] = round(saved_dp, 1)
        out["meta"]["dp_sharded_note"] = (
            f"measured: at {DP}-way data sharding the replicated-dE fused "
            f"variant saves {round(saved_rep, 1)} MiB of compiled-peak "
            f"temps vs unfused (round 5 measured it net-neutral, -116 MiB "
            f"at global batch {DP}) because its backward carries a "
            f"replicated [V={VOCAB}, D={D_MODEL}] f32 dE accumulator; DP "
            f"mode shards that accumulator to [V/{DP}, D] per device and "
            f"saves {round(saved_dp, 1)} MiB — the fused-head win no "
            f"longer degrades under data sharding")
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "..", "RESULTS_fused_ce_memory.json"),
              "w") as f:
        json.dump(out, f, indent=1)
        f.write("\n")
    print(json.dumps(out), flush=True)
    # The claims must be falsifiable: single-chip, the fused step saves at
    # least half the analytic f32 logits footprint; 8-way, DP mode beats
    # unfused by >= 900 MiB of compiled-peak temps (the ISSUE-1 target the
    # replicated variant missed by construction).
    assert saved > 0.5 * logits_mib, (saved, logits_mib)
    if rows_dp:
        assert saved_dp >= 900.0, (saved_dp, saved_rep)
    return 0


if __name__ == "__main__":
    sys.exit(main())

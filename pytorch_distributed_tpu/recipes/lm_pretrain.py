"""Recipe 8 — long-context LM pretraining over composable dp×sp×tp (or
dp×pp, dp×ep) meshes.

Beyond-reference recipe (the reference is image-only): next-token training
of the TransformerLM with the framework's parallelism menu —

- ``--tp N``  tensor parallelism (Megatron-style sharded qkv/proj/fc1/fc2 +
  vocab-sharded embedding; XLA inserts the per-block all-reduces)
- ``--sp N``  sequence parallelism over the ``seq`` axis — ``--sp-impl
  ring`` (KV rotation) or ``a2a`` (Ulysses-style all-to-all re-slice to
  head-sharded; the inner attention sees the full sequence and can run
  the Pallas flash kernel); **composes with --tp**: one ``(data, seq,
  model)`` mesh, heads sharded over ``model`` inside either formulation
- ``--pp N``  pipeline parallelism (GPipe stages over ``pipe``); composes
  with the data axis AND with ``--tp``/``--sp``, which then run *inside*
  each stage (``parallel/tp_stage.py``) — up to all four axes in one
  ``(data, pipe, seq, model)`` mesh
- ``--ep N``  expert parallelism (MoE model variant; exclusive of
  --tp/--sp/--pp, composes with --fsdp: non-expert leaves and the free
  dims of the expert stacks shard over ``data``)
- remaining devices form the ``data`` axis (gradient psum)
- ``--model-config FILE`` a decoder built from a configuration file with
  the catalog's key names (``models/decoder.py``) in place of the
  TransformerLM, trained with the file's AdamW over the ``data`` axis.  The
  file's keys choose the block: latent or plain multi-head attention, dense
  feed-forward or shared and routed experts, two or four norms a block, and
  with ``total_ut_steps`` > 1 a stack that runs several times over the same
  weights, an exit after every pass and the loss weighted over the exits.
  Training only (no serving, no early exit, no vision tower);
  ``--rehearse`` lays the file's CPU preset over it

Examples (8 simulated chips):

    python -m pytorch_distributed_tpu.recipes.lm_pretrain --tp 4 \
        --d-model 512 --n-layers 4 --seq-len 512 -b 16 --steps 50
    python -m pytorch_distributed_tpu.recipes.lm_pretrain --sp 2 --tp 2 \
        --seq-len 8192 -b 8 --steps 20
    python -m pytorch_distributed_tpu.recipes.lm_pretrain --pp 4 \
        --n-layers 8 -b 16 --steps 20
    python -m pytorch_distributed_tpu.recipes.lm_pretrain --rehearse \
        --model-config benchmark/configs/kimi-vl-a3b-ep8.json \
        --seq-len 64 -b 8 --steps 20
    python -m pytorch_distributed_tpu.recipes.lm_pretrain --rehearse \
        --model-config benchmark/configs/ouro-2.6b.json \
        --seq-len 64 -b 8 --steps 20
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp

from pytorch_distributed_tpu.ft.elastic import ElasticSim
from pytorch_distributed_tpu.models.transformer import TransformerLM
from pytorch_distributed_tpu.parallel import MeshSpec, build_mesh, initialize
from pytorch_distributed_tpu.parallel.tp import replicated_like, tp_specs
from pytorch_distributed_tpu.train.lm import (
    LMTrainer,
    SyntheticTokenDataset,
    TextFileDataset,
)
from pytorch_distributed_tpu.utils.compile_cache import enable_compile_cache


def load_decoder_setup(path: str, rehearse: bool = False) -> dict:
    """What ``--model-config FILE`` trains with: the model's
    ``DecoderConfig``, the file's ``optimizer`` group and its fused-loss
    chunk count (``training.fused_ce_chunks``)."""
    import json

    from pytorch_distributed_tpu.models.decoder import DecoderConfig, overlay

    with open(path) as f:
        cfg = json.load(f)
    if rehearse:
        cfg = overlay(cfg, cfg.get("rehearse", {}))
    return {"config": DecoderConfig.from_dict(cfg),
            "optimizer": cfg["optimizer"],
            "fused_ce_chunks": cfg.get("training", {}).get(
                "fused_ce_chunks", 0)}


def decoder_tx(optimizer: dict, lr: float, warmup_steps: int = 0,
               steps: int = 0):
    """The optax ``tx`` of a ``--model-config`` run: the file's AdamW at
    ``lr``, constant, or with ``--warmup-steps`` the recipe's linear
    warm-up and cosine decay to a tenth."""
    from pytorch_distributed_tpu.train.optim import adamw

    if optimizer.get("name", "adamw") != "adamw":
        raise SystemExit(f"no optimizer {optimizer['name']!r}: adamw")
    rate = lr
    if warmup_steps > 0:
        import optax

        rate = optax.warmup_cosine_decay_schedule(
            0.0, lr, warmup_steps, steps, end_value=0.1 * lr)
    return adamw(optimizer, rate)


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description="TPU LM pretraining (long context)")
    p.add_argument("--model-config", type=str, default=None, metavar="FILE",
                   dest="model_config",
                   help="build the decoder from this configuration file "
                        "(models/decoder.py) instead of the TransformerLM, "
                        "and train it with the file's AdamW (--lr overrides "
                        "its rate) and fused-loss chunks; --vocab, "
                        "--d-model, --n-heads, --n-layers, --ep and "
                        "--moe-top-k are then not read")
    p.add_argument("--rehearse", action="store_true",
                   help="with --model-config: lay the file's 'rehearse' "
                        "group (a CPU preset, every ratio kept) over it")
    p.add_argument("--vocab", type=int, default=1024)
    p.add_argument("--d-model", type=int, default=256)
    p.add_argument("--n-heads", type=int, default=8)
    p.add_argument("--n-layers", type=int, default=2)
    p.add_argument("--seq-len", type=int, default=256)
    p.add_argument("-b", "--batch-size", type=int, default=32,
                   help="global batch (sequences)")
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--lr", type=float, default=None,
                   help="learning rate (default 1e-2; with --model-config "
                        "the file's optimizer.lr)")
    p.add_argument("--warmup-steps", type=int, default=0,
                   help=">0: linear warmup then cosine decay to 10%% of "
                        "--lr over --steps (fixed lr otherwise)")
    p.add_argument("--clip-grad-norm", type=float, default=0.0,
                   help=">0: in-graph global-norm gradient clipping")
    p.add_argument("--fused-ce", type=int, default=0, metavar="CHUNKS",
                   help="fused tied-head+CE loss in CHUNKS row blocks "
                        "(ops/fused_ce.py): the [B,L,vocab] logits tensor "
                        "never materializes — big-vocab HBM/memory lever; "
                        "0 = unfused (exact parity tested either way)")
    p.add_argument("--fused-ce-mode", default="auto",
                   choices=("auto", "replicated", "dp", "tp"),
                   dest="fused_ce_mode",
                   help="fused-CE sharding variant: dp keeps the backward's "
                        "dE accumulator as a [V/k, D] vocab-row shard per "
                        "device (data-sharded meshes); tp consumes the "
                        "--tp vocab-sharded embedding directly inside "
                        "shard_map (no replication of e or dE); auto picks "
                        "from the mesh + param specs; replicated = the "
                        "original GSPMD path")
    p.add_argument("--accum-steps", type=int, default=1,
                   help="gradient accumulation microbatches inside the "
                        "compiled step (long-context memory relief; "
                        "redundant with --pp, whose schedule already "
                        "microbatches)")
    p.add_argument("--tp", type=int, default=1, help="tensor-parallel size")
    p.add_argument("--sp", type=int, default=1,
                   help="sequence-parallel (ring) size")
    p.add_argument("--sp-impl", choices=("ring", "a2a"), default="ring",
                   help="SP formulation: ring (ppermute KV rotation, no "
                        "head constraint) or a2a (Ulysses-style all-to-all "
                        "to head-sharded, inner attention sees the full "
                        "sequence and can use the Pallas flash kernel; "
                        "needs n_heads divisible by sp*tp)")
    p.add_argument("--ep", type=int, default=1,
                   help="expert-parallel size (MoE MLPs, one expert/device)")
    p.add_argument("--moe-top-k", type=int, default=1,
                   help="experts per token for --ep (1=Switch, 2=Mixtral-style)")
    p.add_argument("--pp", type=int, default=1,
                   help="pipeline-parallel size (GPipe stages over a 'pipe' "
                        "mesh axis; composes with the data axis, --tp and "
                        "--sp — Megatron TP / ring SP run inside each stage)")
    p.add_argument("--microbatches", type=int, default=0,
                   help="pipeline microbatches (default: pp)")
    p.add_argument("--schedule", choices=("gpipe", "1f1b", "interleaved"),
                   default="gpipe",
                   help="pipeline schedule: gpipe (autodiff, stash O(M)); "
                        "1f1b (manual gradients, stash bounded at 2(pp-1)+1 "
                        "microbatches — parallel/pp_1f1b.py); interleaved "
                        "(virtual-stage 1f1b, --pp-virtual chunks/device: "
                        "bubble/(V) at V x stash — parallel/pp_interleaved.py)")
    p.add_argument("--pp-virtual", type=int, default=2, dest="pp_virtual",
                   help="model chunks per device under --schedule "
                        "interleaved (V; n-layers must divide by pp*V)")
    p.add_argument("--remat", action="store_true",
                   help="checkpoint each pipeline stage (gpipe schedule): "
                        "stash stage inputs only, recompute activations in "
                        "backward")
    p.add_argument("--fsdp", action="store_true",
                   help="shard parameters + optimizer state over the data "
                        "axis (ZeRO-3 layout; GSPMD paths, composes with "
                        "--tp/--sp/--ep and with --pp: stage params gather "
                        "at the pipeline boundary, grads reduce-scatter "
                        "back)")
    p.add_argument("--precision", choices=("fp32", "bf16"), default="bf16")
    p.add_argument("--zero", choices=("none", "wus"), default="none",
                   help="ZeRO-style weight-update sharding (parallel/"
                        "zero.py): 'wus' gives momentum leaves fsdp_specs "
                        "data-axis shardings (composed over the --tp/--pp "
                        "layout) while params stay in their declared "
                        "layout — 1/N optimizer bytes per device, same "
                        "numerics and checkpoint format.  Lighter than "
                        "--fsdp (which also shards the params; that is the "
                        "ZeRO-3 layout, this is ZeRO-1)")
    p.add_argument("--grad-compress", choices=("none", "bf16", "int8", "fp8"),
                   default="none", dest="grad_compress",
                   help="gradient-sync compression (ops/qcomm.py): bf16 "
                        "round-trip cast, or int8/fp8 block quantization "
                        "with error feedback.  The LM step is GSPMD, so "
                        "quantized modes run as a numerics emulation "
                        "under the default GSPMD step (wire bytes "
                        "unchanged; convergence effects real) — add "
                        "--overlap bucketed on a pure-DP run to switch to "
                        "the explicit shard_map step where the wire "
                        "really carries the compressed collectives")
    p.add_argument("--overlap", choices=("none", "bucketed"),
                   default="none",
                   help="comm-overlap scheduler (parallel/overlap.py): "
                        "bucketed runs the pure-DP step as explicit "
                        "shard_map collectives with ~--bucket-mb MiB "
                        "reverse-autodiff grad buckets, each issued as "
                        "its own psum so sync overlaps the remaining "
                        "backward; bit-equal numerics.  Pure DP only "
                        "(no --tp/--sp/--pp/--fsdp/--fused-ce/"
                        "--accum-steps/--zero/--elastic)")
    p.add_argument("--bucket-mb", type=float, default=4.0,
                   dest="bucket_mb", metavar="MIB",
                   help="target gradient bucket size in MiB for --overlap "
                        "bucketed (smaller = more overlap, more "
                        "collectives)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("-p", "--print-freq", type=int, default=10)
    p.add_argument("--checkpoint-dir", type=str, default=None)
    p.add_argument("--resume", type=str, default=None, metavar="PATH",
                   help="resume from a checkpoint: restores params/momentum "
                        "AND the exact step (the ft record), so the run "
                        "continues mid-stream instead of restarting")
    p.add_argument("--save-steps", type=int, default=0, dest="save_steps",
                   metavar="N",
                   help="also checkpoint every N steps (step-granular "
                        "resume: preemption/SIGKILL loses at most N steps); "
                        "0 = end-of-run only")
    p.add_argument("--preempt-signals", type=str, default="term",
                   dest="preempt_signals", metavar="SIGS",
                   help="comma-separated signals that trigger checkpoint-"
                        "and-exit at the next step boundary (default "
                        "'term'; add 'int' for interactive Ctrl-C runs)")
    p.add_argument("--nan-guard", action="store_true", dest="nan_guard",
                   help="divergence guard: skip non-finite steps in-graph; "
                        "after --ft-rollback-k consecutive bad steps, roll "
                        "back to the last-good state with an LR backoff")
    p.add_argument("--ft-rollback-k", type=int, default=3,
                   dest="ft_rollback_k", metavar="K",
                   help="consecutive non-finite steps before rollback")
    p.add_argument("--ft-check-every", type=int, default=10,
                   dest="ft_check_every", metavar="N",
                   help="drain the guard's buffered flags every N steps "
                        "(one amortized host sync)")
    p.add_argument("--ft-lr-backoff", type=float, default=0.5,
                   dest="ft_lr_backoff", metavar="F",
                   help="LR multiplier applied at each rollback")
    p.add_argument("--elastic", action="store_true", dest="elastic",
                   help="elastic training (ft/elastic.py): on rank loss "
                        "re-mesh to the survivors and continue from the "
                        "last-good snapshot; on rank join re-shard and "
                        "re-admit (plain-dp meshes only)")
    p.add_argument("--min-ranks", type=int, default=1, dest="min_ranks",
                   metavar="N",
                   help="elastic shrink floor: refuse changes that would "
                        "take the data axis below N ranks")
    p.add_argument("--rescale-lr", choices=("none", "linear", "sqrt"),
                   default="none", dest="rescale_lr",
                   help="LR/global-batch rule across an elastic world "
                        "change: none = global batch constant, LR "
                        "untouched; linear/sqrt = per-rank batch constant, "
                        "LR scaled by (new/old) or sqrt(new/old)")
    p.add_argument("--dataset-length", type=int, default=4096)
    p.add_argument("--text-glob", type=str, default=None,
                   help="train on real files: byte-level LM over this glob "
                        "(e.g. 'src/**/*.py'); forces --vocab 256 and "
                        "replaces the synthetic dataset")
    p.add_argument("--metrics-jsonl", type=str, default=None,
                   dest="metrics_jsonl", metavar="PATH",
                   help="append one structured JSON record per train step "
                        "(step-time EMA/p50/p95, tokens/s, loss, lr, "
                        "in-graph grad/param norms) to this file; "
                        "summarize with scripts/obs_report.py")
    p.add_argument("--hb-dir", type=str, default=None, dest="hb_dir",
                   metavar="DIR",
                   help="shared heartbeat directory: each mesh process "
                        "appends {pid, step, t} beats; obs_report.py flags "
                        "stragglers by step lag / beat age")
    p.add_argument("--hb-interval", type=float, default=5.0,
                   dest="hb_interval_s", metavar="SEC",
                   help="minimum seconds between heartbeats (default 5)")
    p.add_argument("--mfu", action="store_true",
                   help="report per-step MFU/HFU in the metrics JSONL: the "
                        "analytic LM FLOPs model (obs/flops.py — fused-CE, "
                        "remat, and pipeline schedules accounted) over the "
                        "chips' peak FLOPs")
    p.add_argument("--goodput", action="store_true",
                   help="track the goodput/badput ledger live (nan-skips, "
                        "rollback discards, preemption gaps, recompiles, "
                        "stalls) and print the summary at end of fit")
    p.add_argument("--watch-recompiles", action="store_true",
                   dest="watch_recompiles",
                   help="recompile watchdog (obs/watchdog.py): flag any "
                        "post-warmup recompilation of the jitted step as "
                        "an anomaly event via jax.monitoring")
    p.add_argument("--comm-ledger", type=str, default=None,
                   dest="comm_ledger", metavar="PATH",
                   help="write the step's itemized communication ledger "
                        "(per-collective bytes/fan-out/scope, obs/comms.py) "
                        "to PATH and stamp model_comm_bytes/comm_wire_bytes/"
                        "collective_count into each metrics record; costs "
                        "one extra AOT compile of the step")
    p.add_argument("--mem-ledger", type=str, default=None,
                   dest="mem_ledger", metavar="PATH",
                   help="write the step's static HBM memory ledger "
                        "(live-range watermark, top buffers at peak, "
                        "class/phase breakdown, obs/memory.py) to PATH and "
                        "stamp mem_peak_bytes into each metrics record; "
                        "rides the --comm-ledger AOT lowering so the pair "
                        "costs one shared compile")
    p.add_argument("--lowering-cache", type=str, default=None,
                   dest="lowering_cache", metavar="DIR",
                   help="persist the ledger AOT lowering's artifacts "
                        "(<step>.hlo + <step>.json, analysis/lowering.py "
                        "layout) under DIR for post-hoc text-only "
                        "re-analysis")
    p.add_argument("--flight-rec", type=str, default=None,
                   dest="flight_rec", metavar="DIR",
                   help="flight recorder (obs/flightrec.py): bounded "
                        "in-memory ring of step/collective/ft events "
                        "dumped to DIR/flightrec_rank<k>.json on any "
                        "death path (signal, rollback, checkpoint "
                        "corruption, unhandled exception, hang watchdog); "
                        "merge dumps with scripts/postmortem.py")
    p.add_argument("--hang-timeout", type=float, default=30.0,
                   dest="hang_timeout", metavar="SEC",
                   help="hang-watchdog floor: flag a step exceeding "
                        "max(SEC, 4×p95), emit a `hang` ft_event with the "
                        "last-entered collective, and dump the flight "
                        "ring pre-mortem (needs --flight-rec)")
    p.add_argument("--metrics-port", type=int, default=0,
                   dest="metrics_port", metavar="PORT",
                   help="serve live Prometheus metrics on PORT + rank "
                        "(obs/export.py; one daemon thread per rank, "
                        "latest drained record; 0 disables; watch the "
                        "fleet with scripts/obs_live.py)")
    p.add_argument("--alerts", type=str, default=None, dest="alerts",
                   metavar="RULES",
                   help="declarative alert rules (obs/alerts.py): a JSON "
                        "rules file or 'default' for the built-in set; "
                        "firing alerts are booked as `alert` ft_events "
                        "in the metrics JSONL and exported to /metrics")
    p.add_argument("--step-attr", action="store_true", dest="step_attr",
                   help="exact per-step wall-time attribution "
                        "(obs/stepattr.py): stamp attr_* fields — compute "
                        "/ exposed_comm / host_sync / data_wait / other, "
                        "summing to step_time exactly — into every "
                        "metrics record; analyze with "
                        "scripts/obs_roofline.py")
    p.add_argument("--profile-dir", type=str, default=None,
                   dest="profile_dir", metavar="DIR",
                   help="write an XPlane trace of the run there, with the "
                        "loop's host spans (spans.jsonl) and the compiled "
                        "step's scope map (scopes.json: which scope() and "
                        "phase each device instruction belongs to)")
    p.add_argument("--profile-steps", type=str, default=None,
                   dest="profile_steps", metavar="I[:J]",
                   help="trace only that step range (past compilation)")
    p.add_argument("--eval-every", type=int, default=0,
                   help="run held-out eval (loss/ppl) every N steps; "
                        "0 = end-of-run only")
    p.add_argument("--eval-batches", type=int, default=8)
    p.add_argument("--no-eval", action="store_true",
                   help="disable the held-out eval entirely")
    p.add_argument("--generate", type=int, default=0, metavar="N",
                   help="after training, greedy-decode N tokens from a "
                        "dataset prompt (plain dp runs only)")
    return p


def main(argv=None) -> float:
    args = build_parser().parse_args(argv)
    enable_compile_cache()
    ctx = initialize()
    n = jax.device_count()
    decoder = None
    if args.model_config:
        if max(args.tp, args.sp, args.pp, args.ep) > 1 or args.fsdp:
            raise SystemExit("--model-config trains over the data axis "
                             "only: drop --tp/--sp/--pp/--ep/--fsdp")
        if args.generate:
            raise SystemExit("--model-config has no decoding path")
        decoder = load_decoder_setup(args.model_config, args.rehearse)
        args.vocab = decoder["config"].vocab_size
        args.fused_ce = args.fused_ce or decoder["fused_ce_chunks"]
        if args.lr is None:
            args.lr = decoder["optimizer"]["lr"]
    elif args.rehearse:
        raise SystemExit("--rehearse needs --model-config")
    if args.lr is None:
        args.lr = 1e-2
    if args.ep > 1 and (args.tp > 1 or args.sp > 1 or args.pp > 1):
        raise SystemExit("--ep is exclusive (MoE model variant); "
                         "--tp composes with --sp or --pp")
    if args.warmup_steps >= args.steps and args.warmup_steps > 0:
        raise SystemExit(f"--warmup-steps {args.warmup_steps} must be < "
                         f"--steps {args.steps} (no room for cosine decay)")
    if args.sp > 1 and args.seq_len % args.sp:
        raise SystemExit(f"--seq-len {args.seq_len} not divisible by "
                         f"--sp {args.sp}")
    if args.schedule in ("1f1b", "interleaved") and args.pp <= 1:
        raise SystemExit(f"--schedule {args.schedule} requires --pp > 1")
    if args.schedule in ("1f1b", "interleaved") and (args.tp > 1
                                                     or args.sp > 1):
        raise SystemExit(f"--schedule {args.schedule} supports plain "
                         "stages; use gpipe for TP/SP-in-stage")
    if args.schedule == "interleaved":
        micro = args.microbatches or args.pp
        if micro % args.pp:
            raise SystemExit(f"--schedule interleaved needs --microbatches "
                             f"{micro} divisible by --pp {args.pp}")
        if args.n_layers % (args.pp * args.pp_virtual):
            raise SystemExit(f"--n-layers {args.n_layers} not divisible by "
                             f"pp*V = {args.pp * args.pp_virtual}")
    if args.remat and args.pp <= 1:
        raise SystemExit("--remat applies to the pipeline stages "
                         "(requires --pp > 1)")
    if args.fused_ce and args.pp > 1:
        raise SystemExit("--fused-ce applies to the non-pipelined loss "
                         "path (the pipeline schedules own their loss "
                         "head); drop --pp or --fused-ce")
    if args.accum_steps > 1 and args.pp > 1:
        raise SystemExit("--accum-steps with --pp is redundant: the pipeline "
                         "schedule already microbatches; raise "
                         "--microbatches instead")
    if n % (args.tp * args.sp * args.ep * args.pp):
        raise SystemExit(f"{n} devices not divisible by tp*sp*ep*pp")
    if args.pp > 1 and args.n_layers % args.pp:
        raise SystemExit(f"--n-layers {args.n_layers} not divisible by "
                         f"--pp {args.pp} stages")
    if args.pp > 1:
        micro = args.microbatches or args.pp
        # data axis of the pp(×sp)(×tp) mesh
        pp_dp = n // (args.pp * args.tp * args.sp)
        if args.batch_size % micro:
            raise SystemExit(f"-b {args.batch_size} not divisible by "
                             f"{micro} pipeline microbatches")
        if (args.batch_size // micro) % pp_dp:
            raise SystemExit(
                f"per-microbatch batch {args.batch_size // micro} not "
                f"divisible by the data axis ({pp_dp} replicas)")
    if args.moe_top_k < 1:
        raise SystemExit(f"--moe-top-k must be >= 1, got {args.moe_top_k}")
    if args.moe_top_k > 1 and args.ep <= 1:
        raise SystemExit("--moe-top-k requires --ep > 1 (it selects experts "
                         "per token in the MoE model variant)")
    if args.generate > 0 and (args.tp > 1 or args.sp > 1 or args.ep > 1
                              or args.pp > 1):
        raise SystemExit("--generate supports plain dp runs only")
    if args.elastic and (args.tp > 1 or args.sp > 1 or args.ep > 1
                         or args.pp > 1 or args.fsdp):
        raise SystemExit("--elastic re-meshes the data axis and supports "
                         "plain dp runs only (drop --tp/--sp/--ep/--pp/"
                         "--fsdp)")
    if not args.elastic and args.rescale_lr != "none":
        raise SystemExit("--rescale-lr applies to elastic world changes; "
                         "add --elastic")
    if args.overlap == "bucketed" and (
            args.tp > 1 or args.sp > 1 or args.ep > 1 or args.pp > 1
            or args.fsdp or args.fused_ce or args.accum_steps > 1
            or args.zero != "none" or args.elastic):
        raise SystemExit("--overlap bucketed runs the explicit shard_map "
                         "pure-DP step only; drop --tp/--sp/--ep/--pp/"
                         "--fsdp/--fused-ce/--accum-steps/--zero/--elastic")
    if args.sp_impl == "a2a" and args.sp > 1:
        if args.pp > 1:
            raise SystemExit("--sp-impl a2a does not run inside pipeline "
                             "stages yet; use the ring schedule with --pp")
        if args.n_heads % (args.sp * args.tp):
            raise SystemExit(f"--sp-impl a2a shards heads: --n-heads "
                             f"{args.n_heads} must be divisible by "
                             f"sp*tp = {args.sp * args.tp}")
    if args.tp > 1 and args.sp > 1 and args.n_heads % args.tp:
        # Composed with ring SP the attention heads are explicitly sharded
        # over 'model' (ring.py shard_map specs); pure GSPMD TP has no such
        # constraint.
        raise SystemExit(f"--n-heads {args.n_heads} not divisible by "
                         f"--tp {args.tp} (required when combined with --sp)")
    dtype = jnp.bfloat16 if args.precision == "bf16" else jnp.float32
    if args.text_glob:
        args.vocab = TextFileDataset.vocab  # before the model is built

    if args.ep > 1:
        mesh = build_mesh(MeshSpec(("data", "expert"), (n // args.ep, args.ep)))
        model = TransformerLM(
            vocab_size=args.vocab, d_model=args.d_model, n_heads=args.n_heads,
            n_layers=args.n_layers, dtype=dtype, moe_experts=args.ep,
            moe_top_k=args.moe_top_k,
        )
        specs = "ep"
    elif args.pp > 1:
        from pytorch_distributed_tpu.models.pipeline_lm import (
            PipelinedTransformerLM,
        )

        axes = ["data", "pipe"]
        shape = [n // (args.pp * args.tp * args.sp), args.pp]
        if args.sp > 1:  # ring SP inside each stage (tp_stage.py)
            axes.append("seq")
            shape.append(args.sp)
        if args.tp > 1:  # Megatron TP inside each stage (tp_stage.py)
            axes.append("model")
            shape.append(args.tp)
        mesh = build_mesh(MeshSpec(tuple(axes), tuple(shape)))
        model = PipelinedTransformerLM(
            vocab_size=args.vocab, d_model=args.d_model,
            n_heads=args.n_heads, n_layers=args.n_layers,
            n_stages=args.pp,
            n_microbatches=args.microbatches or args.pp,
            mesh=mesh, dtype=dtype, tp_size=args.tp, sp_size=args.sp,
            schedule=args.schedule, remat=args.remat,
            n_virtual=(args.pp_virtual
                       if args.schedule == "interleaved" else 1),
        )
        specs = "pp"
    else:
        # Composable dp × sp × tp mesh: the data axis takes the remaining
        # devices; 'model' is innermost so Megatron's per-block all-reduces
        # ride the fastest ICI hops (parallel/mesh.py note).
        axes, shape = ["data"], [n // (args.tp * args.sp)]
        if args.sp > 1:
            axes.append("seq")
            shape.append(args.sp)
        if args.tp > 1:
            axes.append("model")
            shape.append(args.tp)
        mesh = build_mesh(MeshSpec(tuple(axes), tuple(shape)))
        if decoder is not None:
            from pytorch_distributed_tpu.models.decoder import DecoderLM

            model = DecoderLM(decoder["config"], dtype=dtype)
        else:
            model = TransformerLM(
                vocab_size=args.vocab, d_model=args.d_model,
                n_heads=args.n_heads, n_layers=args.n_layers, dtype=dtype,
                mesh=mesh if args.sp > 1 else None, ring=args.sp > 1,
                sp_impl=args.sp_impl,
            )
        specs = "tp" if args.tp > 1 else None

    if args.text_glob:
        # hold out the 10% tail for eval only when eval will run
        train_span = (0.0, 1.0) if args.no_eval else (0.0, 0.9)
        try:
            dataset = TextFileDataset(args.text_glob, args.seq_len,
                                      span=train_span)
        except ValueError as e:
            raise SystemExit(
                f"--text-glob corpus too small for --seq-len "
                f"{args.seq_len} ({e}); add files or shorten --seq-len"
            ) from e
    else:
        dataset = SyntheticTokenDataset(
            args.dataset_length, args.seq_len, args.vocab, seed=args.seed
        )
    with mesh:
        # Init batch must cover the data axis (the ring shard_map divides the
        # batch dim during init tracing too).
        tokens0 = jnp.zeros((dict(mesh.shape).get("data", 1), args.seq_len),
                            jnp.int32)
        params_shape = None
        if specs in ("tp", "ep", "pp") or args.fsdp:
            params_shape = jax.eval_shape(
                lambda: model.init(jax.random.PRNGKey(args.seed), tokens0)
            )["params"]
        if specs in ("tp", "ep", "pp"):
            if specs == "tp":
                specs = tp_specs(params_shape)
            elif specs == "pp":
                from pytorch_distributed_tpu.models.pipeline_lm import pp_specs

                specs = pp_specs(
                    params_shape,
                    model_axis="model" if args.tp > 1 else None,
                )
            else:
                from pytorch_distributed_tpu.models.moe import moe_specs

                specs = moe_specs(params_shape)
        if args.fsdp:
            from pytorch_distributed_tpu.parallel.fsdp import fsdp_specs

            specs = fsdp_specs(params_shape, mesh, base_specs=specs)
        if args.no_eval:
            eval_dataset = None
        elif args.text_glob:
            try:
                eval_dataset = TextFileDataset(  # held-out corpus tail
                    args.text_glob, args.seq_len, span=(0.9, 1.0))
            except ValueError as e:
                raise SystemExit(
                    f"the held-out 10% corpus tail is too small for "
                    f"--seq-len {args.seq_len} ({e}); add files, shorten "
                    f"--seq-len, or pass --no-eval to train on the full "
                    f"corpus") from e
        else:
            eval_dataset = SyntheticTokenDataset(
                max(args.dataset_length // 10, args.batch_size),
                args.seq_len, args.vocab, seed=args.seed + 1,
            )
        schedule = None
        if args.warmup_steps > 0:
            from pytorch_distributed_tpu.train.lm import warmup_cosine_lr

            schedule = warmup_cosine_lr(args.lr, args.warmup_steps, args.steps)
        tx = None
        if decoder is not None:
            # the schedule lives inside the tx (make_lm_train_step): the
            # host-side one only labels the log
            tx = decoder_tx(decoder["optimizer"], args.lr,
                            args.warmup_steps, args.steps)
        # Preemption guard (previously only the image Trainer self-
        # installed one; the LM recipe ran unguarded): --preempt-signals
        # SIGTERM (pod reclaim) by default, SIGINT opt-in for interactive
        # runs.  Installed here (main thread — a Python signal-handler
        # restriction) and chained/uninstalled around fit.
        import threading

        from pytorch_distributed_tpu.utils.preempt import (
            PreemptionGuard,
            parse_signals,
        )

        guard = None
        if threading.current_thread() is threading.main_thread():
            guard = PreemptionGuard(
                signals=parse_signals(args.preempt_signals)).install()
        trainer = LMTrainer(
            model, mesh, dataset, args.batch_size, lr=args.lr,
            param_specs=specs, seed=args.seed, is_primary=ctx.is_primary,
            checkpoint_dir=args.checkpoint_dir,
            eval_dataset=eval_dataset, eval_every=args.eval_every,
            eval_batches=args.eval_batches,
            lr_schedule=schedule, clip_grad_norm=args.clip_grad_norm,
            accum_steps=args.accum_steps, fused_ce_chunks=args.fused_ce,
            fused_ce_mode=args.fused_ce_mode,
            metrics_jsonl=args.metrics_jsonl, hb_dir=args.hb_dir,
            hb_interval_s=args.hb_interval_s,
            mfu=args.mfu, goodput=args.goodput,
            watch_recompiles=args.watch_recompiles,
            comm_ledger=args.comm_ledger,
            mem_ledger=args.mem_ledger,
            lowering_cache=args.lowering_cache,
            save_steps=args.save_steps, resume=args.resume,
            nan_guard=args.nan_guard, ft_rollback_k=args.ft_rollback_k,
            ft_check_every=args.ft_check_every,
            ft_lr_backoff=args.ft_lr_backoff,
            preempt=guard,
            grad_compress=args.grad_compress,
            zero=args.zero,
            overlap=args.overlap,
            bucket_mb=args.bucket_mb,
            elastic=(ElasticSim(dict(mesh.shape).get("data", 1),
                                min_ranks=args.min_ranks)
                     if args.elastic else None),
            rescale_lr=args.rescale_lr,
            flight_rec=args.flight_rec,
            hang_timeout=args.hang_timeout,
            metrics_port=args.metrics_port,
            alerts=args.alerts,
            step_attr=args.step_attr,
            tx=tx,
            profile_dir=args.profile_dir,
            profile_steps=args.profile_steps,
        )
        try:
            final_loss = trainer.fit(args.steps, print_freq=args.print_freq)
        finally:
            if guard is not None:
                guard.uninstall()
        if args.generate > 0:  # plain-dp only, validated with the args above
            import jax as _jax
            import numpy as _np

            from pytorch_distributed_tpu.models.generate import greedy_generate

            prompt = dataset.batch(0, 1)[:, : min(16, args.seq_len // 2)]
            params = _jax.device_get(trainer.state.params)
            toks = greedy_generate(
                params, prompt, args.generate, vocab_size=args.vocab,
                d_model=args.d_model, n_heads=args.n_heads,
                n_layers=args.n_layers, dtype=dtype,
            )
            print(" * Generated:", " ".join(map(str, _np.asarray(toks)[0])),
                  flush=True)
    print(f" * Final loss {final_loss:.4f}", flush=True)
    return final_loss


if __name__ == "__main__":
    main()

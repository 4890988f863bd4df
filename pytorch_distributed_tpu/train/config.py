"""CLI / config: the reference's 15-flag surface as one dataclass.

Flag names, shorthands, and defaults mirror reference distributed.py:25-102
(``--data -a -j --epochs --start-epoch -b --lr --momentum --wd -p -e
--pretrained --seed``), with the reference's per-recipe extras available as
opt-ins (``--dist-file`` from distributed_slurm_main.py:102-105) and
TPU-native additions the recipes need:

- ``--precision {fp32,bf16}``   — the apex-AMP slot (SURVEY.md §7.1)
- ``--synthetic``               — synthetic dataset (no ImageNet on disk)
- ``--image-size``              — train crop size (default 224)
- ``--resume PATH``             — the load path the reference lacks (§5.3)
- ``--checkpoint-dir``          — where checkpoints land

Like the reference, the global batch is divided by world size in the driver
(reference distributed.py:146), not here.
"""

from __future__ import annotations

import argparse
import dataclasses
from typing import Optional

from pytorch_distributed_tpu import models


@dataclasses.dataclass
class Config:
    data: str = "/home/zhangzhi/Data/exports/ImageNet2012"
    arch: str = "resnet18"
    workers: int = 4
    worker_type: str = "thread"   # "thread" | "process" (GIL-proof PIL path)
    epochs: int = 90
    start_epoch: int = 0
    batch_size: int = 3200        # GLOBAL batch (reference semantics)
    lr: float = 0.1
    # "step" = the reference's adjust_learning_rate (0.1x every 30 epochs,
    # distributed.py:374-378); "cosine" = warmup+cosine over --epochs.
    lr_schedule: str = "step"
    lr_warmup_epochs: int = 0
    momentum: float = 0.9
    weight_decay: float = 1e-4
    print_freq: int = 10
    evaluate: bool = False
    pretrained: bool = False
    seed: Optional[int] = None
    # per-recipe extras / TPU-native additions
    dist_file: Optional[str] = None
    # None = "recipe decides" (apex/tpu_native default to bf16); an explicit
    # --precision flag always wins over the recipe default.
    precision: Optional[str] = None
    synthetic: bool = False
    synthetic_length: int = 1280
    wire: str = "f32"
    # Gradient wire format for the DP sync (ops/qcomm.py): bf16 casts the
    # psum operand (the old wire_dtype knob); int8/fp8 run the per-block
    # quantized all-reduce with error feedback.  None = "recipe decides"
    # (horovod defaults to bf16), mirroring the precision convention.
    grad_compress: Optional[str] = None
    # ZeRO-style weight-update sharding (parallel/zero.py): "wus" shards the
    # SGD momentum 1/N over the data axis, reduce-scatters gradients, and
    # all-gathers the parameter delta once per step — (N-1)/N of the
    # optimizer+synced-gradient bytes reclaimed per device at equal wire
    # cost.  None = "recipe decides" (all recipes currently default to the
    # replicated-DP "none"), mirroring the grad_compress convention.
    zero: Optional[str] = None
    # Comm-overlap scheduler (parallel/overlap.py): "bucketed" splits the
    # explicit grad sync into ~bucket_mb-MiB reverse-autodiff buckets so
    # each bucket's collective can run concurrently with the remaining
    # backward (bit-equal numerics; requires the explicit-collectives step).
    overlap: str = "none"
    bucket_mb: float = 4.0
    accum_steps: int = 1
    local_rank: int = -1  # launch-line parity only; unused on TPU
    image_size: int = 224
    num_classes: int = 1000
    # ResNet stem variant: "space_to_depth" is the MLPerf-style packed stem
    # (identical math/params, faster MXU tiling); other archs ignore it.
    stem: str = "conv7"
    # Cross-replica SyncBN for the explicit-collectives (shard_map) step:
    # psum the BN moments over the data axis so statistics cover the
    # global batch, matching GSPMD's implicit semantics.  ≙ torch
    # nn.SyncBatchNorm — the capability torch users reach for at small
    # per-device batch.  No effect under GSPMD (already synced).
    sync_bn: bool = False
    # LM-family loss head (recipes/lm_pretrain.py forwards these): chunked
    # fused tied-head+CE (ops/fused_ce.py) and its sharding variant —
    # auto picks dp/tp from the mesh + param specs (resolve_fused_ce_mode).
    fused_ce_chunks: int = 0
    fused_ce_mode: str = "auto"
    resume: Optional[str] = None
    # Default under runs/ so checkpoints never land in the repo root
    # (workspace-hygiene; save_checkpoint creates the directory).
    checkpoint_dir: str = "runs"
    ckpt_backend: str = "msgpack"
    # Fault tolerance (ft/): mid-epoch checkpoint cadence (0 = epoch
    # boundaries only — a preemption then loses the partial epoch; N > 0
    # bounds the loss to N steps even under SIGKILL), the in-graph
    # non-finite guard with its rollback policy, and which signals the
    # preemption guard traps.
    save_steps: int = 0
    nan_guard: bool = False
    ft_rollback_k: int = 3
    ft_check_every: int = 10
    ft_lr_backoff: float = 0.5
    preempt_signals: str = "term"
    # Elastic training (ft/elastic.py): re-mesh on rank loss/join and
    # re-shard state from the last-good snapshot.  min_ranks is the shrink
    # floor; rescale_lr picks the LR/global-batch rule across a world
    # change ("none" holds the global batch constant and the LR untouched;
    # "linear"/"sqrt" hold the per-rank batch constant and scale the LR).
    elastic: bool = False
    min_ranks: int = 1
    rescale_lr: str = "none"
    epoch_csv: Optional[str] = None
    profile_dir: Optional[str] = None
    # Profiler capture windows (obs/trace.py ProfileWindow): 'E' or 'A:B'
    # epochs, optionally narrowed to an in-epoch 'I' or 'I:J' step range —
    # steady-state traces instead of the warm-up-only epoch-0 capture.
    profile_epochs: Optional[str] = None
    profile_steps: Optional[str] = None
    telemetry_csv: Optional[str] = None
    # Unified observability (obs/): one structured JSON record per train
    # step, and per-process heartbeats for cross-process straggler
    # detection (scripts/obs_report.py folds all of it into one summary).
    metrics_jsonl: Optional[str] = None
    hb_dir: Optional[str] = None
    hb_interval_s: float = 5.0
    # Efficiency accounting (obs/flops.py, obs/goodput.py, obs/watchdog.py):
    # per-step MFU/HFU from the analytic FLOPs model, the live goodput/
    # badput ledger, and the jax.monitoring recompile watchdog.
    mfu: bool = False
    goodput: bool = False
    watch_recompiles: bool = False
    # Communication ledger (obs/comms.py): AOT-compile the step once at
    # fit() start, itemize every collective (bytes/fan-out/scope), write
    # the ledger JSON next to the run, and stamp model_comm_bytes /
    # comm_wire_bytes / collective_count into each metrics record.
    # Opt-in because the AOT lowering does not share the jit call cache
    # in jax 0.4.x — it costs one extra compile of the step.
    comm_ledger: Optional[str] = None
    # Memory ledger (obs/memory.py): static per-device HBM watermark from
    # the same AOT lowering as the comm ledger (one shared compile for
    # both), with top-buffers-at-peak attribution and class/phase
    # breakdown written as JSON next to the run.
    mem_ledger: Optional[str] = None
    # Lowering-service artifact dir (analysis/lowering.py): the ledger
    # AOT compile additionally persists the step's <name>.hlo/<name>.json
    # pair here so post-hoc tooling re-analyzes text instead of
    # recompiling.
    lowering_cache: Optional[str] = None
    # Flight recorder (obs/flightrec.py): per-rank bounded event ring
    # dumped to flightrec_rank<k>.json in this directory on any death
    # path (signal / rollback / checkpoint corruption / unhandled fit
    # exception / hang watchdog); merge with scripts/postmortem.py.
    flight_rec: Optional[str] = None
    # Collective-hang watchdog floor: a step exceeding
    # max(hang_timeout, 4×p95) triggers a `hang` ft_event + pre-mortem
    # ring dump.  Only active with flight_rec set.
    hang_timeout: float = 30.0
    # Live telemetry plane (obs/export.py): serve the latest drained
    # metrics record as Prometheus text exposition on this port (rank k
    # binds metrics_port + k).  0 = off.  Scrape with scripts/obs_live.py.
    metrics_port: int = 0
    # Declarative alert rules (obs/alerts.py): a JSON rules file, or the
    # literal "default" for the built-in anchor-free set.  Firing alerts
    # are booked as `alert` ft_events in the metrics JSONL.
    alerts: Optional[str] = None
    # Exact per-step wall-time attribution (obs/stepattr.py): stamp
    # attr_* component fields into every metrics record and carry a
    # data_wait EMA in heartbeats.  Costs one explicit block per step
    # (<2% step p50) — the price of the identity closing exactly.
    step_attr: bool = False
    # derived at runtime (reference args.nprocs, distributed.py:114)
    nprocs: int = 1


def build_parser(description: str = "TPU ImageNet Training") -> argparse.ArgumentParser:
    d = Config()
    names = models.model_names()
    p = argparse.ArgumentParser(description=description)
    p.add_argument("--data", metavar="DIR", default=d.data, help="path to dataset")
    p.add_argument("-a", "--arch", metavar="ARCH", default=d.arch, choices=names,
                   help="model architecture: " + " | ".join(names) + f" (default: {d.arch})")
    p.add_argument("-j", "--workers", default=d.workers, type=int, metavar="N",
                   help="number of data loading workers (default: 4)")
    p.add_argument("--worker-type", default=d.worker_type,
                   choices=("thread", "process"), dest="worker_type",
                   help="loader workers: threads (native decode path) or "
                        "spawned processes (GIL-proof Python/PIL decode, "
                        "reference DataLoader worker semantics)")
    p.add_argument("--epochs", default=d.epochs, type=int, metavar="N",
                   help="number of total epochs to run")
    p.add_argument("--start-epoch", default=d.start_epoch, type=int, metavar="N",
                   help="manual epoch number (useful on restarts)")
    p.add_argument("-b", "--batch-size", default=d.batch_size, type=int, metavar="N",
                   help="mini-batch size: total batch size across all chips")
    p.add_argument("--lr", "--learning-rate", default=d.lr, type=float,
                   metavar="LR", help="initial learning rate", dest="lr")
    p.add_argument("--lr-schedule", default=d.lr_schedule,
                   choices=("step", "cosine"), dest="lr_schedule",
                   help="step = reference 0.1x-every-30-epochs decay; "
                   "cosine = warmup+cosine over --epochs")
    p.add_argument("--lr-warmup-epochs", default=d.lr_warmup_epochs, type=int,
                   dest="lr_warmup_epochs",
                   help="linear LR warmup epochs (cosine schedule)")
    p.add_argument("--momentum", default=d.momentum, type=float, metavar="M",
                   help="momentum")
    p.add_argument("--wd", "--weight-decay", default=d.weight_decay, type=float,
                   metavar="W", help="weight decay (default: 1e-4)", dest="weight_decay")
    p.add_argument("-p", "--print-freq", default=d.print_freq, type=int, metavar="N",
                   help="print frequency (default: 10)")
    p.add_argument("-e", "--evaluate", dest="evaluate", action="store_true",
                   help="evaluate model on validation set")
    p.add_argument("--pretrained", dest="pretrained", action="store_true",
                   help="use pre-trained model")
    p.add_argument("--seed", default=d.seed, type=int,
                   help="seed for initializing training.")
    p.add_argument("--dist-file", default=d.dist_file, type=str,
                   help="rendezvous file for multi-host bootstrap (slurm recipe)")
    p.add_argument("--precision", default=d.precision, choices=("fp32", "bf16"),
                   help="compute precision policy (bf16 = apex-AMP slot); "
                   "unset = recipe default")
    p.add_argument("--synthetic", action="store_true",
                   help="use a synthetic dataset instead of --data")
    p.add_argument("--synthetic-length", default=d.synthetic_length, type=int,
                   help="samples per synthetic epoch")
    p.add_argument("--image-size", default=d.image_size, type=int,
                   help="train crop size (default 224)")
    p.add_argument("--num-classes", default=d.num_classes, type=int,
                   help="number of classes (synthetic mode; ImageFolder infers)")
    p.add_argument("--accum-steps", default=d.accum_steps, type=int,
                   help="split each batch into N microbatches, accumulate "
                   "gradients in-graph, apply one update (fits the default "
                   "global batch 3200 on small chip counts)")
    p.add_argument("--local_rank", default=-1, type=int,
                   help="accepted for reference launch-line parity "
                   "(distributed.py:73-76); process identity on TPU comes "
                   "from PTD_TPU_PROCESS_ID / pod metadata instead")
    p.add_argument("--wire", default=d.wire,
                   choices=("f32", "u8host", "u8", "native"),
                   help="input pipeline format: f32 = per-sample normalize "
                   "(reference-shaped); u8host = native C++ batch "
                   "flip+normalize; u8 = uint8 over the wire, normalize on "
                   "device (4x fewer host->device bytes); native = C++ JPEG "
                   "decode+crop+resize AND uint8 wire (full native path)")
    p.add_argument("--grad-compress", default=d.grad_compress,
                   choices=("none", "bf16", "int8", "fp8"),
                   dest="grad_compress",
                   help="gradient wire format for the DP sync: bf16 casts "
                   "the all-reduce operand (Horovod fp16-compression "
                   "analogue); int8/fp8 = per-block quantized all-reduce "
                   "with error feedback (ops/qcomm.py) — true wire "
                   "compression on the explicit-collectives step, numerics "
                   "emulation under GSPMD; unset = recipe default")
    p.add_argument("--zero", default=d.zero, choices=("none", "wus"),
                   help="ZeRO-style weight-update sharding "
                   "(arXiv:2004.13336): wus reduce-scatters gradients, "
                   "keeps optimizer state sharded 1/N over the data axis, "
                   "updates on the shard, and all-gathers the parameter "
                   "delta — ~(N-1)/N of optimizer+gradient bytes reclaimed "
                   "per device; composes with --grad-compress (both wire "
                   "hops quantized); unset = recipe default (none)")
    p.add_argument("--overlap", default=d.overlap,
                   choices=("none", "bucketed"),
                   help="comm-overlap scheduler (parallel/overlap.py): "
                   "bucketed splits the explicit grad sync into "
                   "~--bucket-mb MiB reverse-autodiff buckets issued as "
                   "separate collectives that overlap the remaining "
                   "backward; bit-equal numerics (requires the "
                   "explicit-collectives step — horovod recipe, or "
                   "lm_pretrain pure-DP)")
    p.add_argument("--bucket-mb", default=d.bucket_mb, type=float,
                   dest="bucket_mb", metavar="MIB",
                   help="target gradient bucket size in MiB for --overlap "
                   "bucketed (smaller = more overlap, more collectives)")
    p.add_argument("--resume", default=d.resume, type=str, metavar="PATH",
                   help="path to checkpoint to resume from")
    p.add_argument("--checkpoint-dir", default=d.checkpoint_dir, type=str,
                   help="directory for checkpoint files")
    p.add_argument("--ckpt-backend", default=d.ckpt_backend,
                   choices=("msgpack", "orbax"), dest="ckpt_backend",
                   help="msgpack = single-file portable (default); orbax = "
                   "async sharded per-process writes (multi-host TP/SP scale)")
    p.add_argument("--save-steps", default=d.save_steps, type=int,
                   dest="save_steps", metavar="N",
                   help="also checkpoint every N train steps (step-granular "
                   "resume: preemption/SIGKILL loses at most N steps instead "
                   "of the whole epoch); 0 = epoch boundaries only")
    p.add_argument("--nan-guard", action="store_true", dest="nan_guard",
                   help="divergence guard: detect non-finite loss/grad-norm "
                   "inside the compiled step, skip the bad batch's update, "
                   "and after --ft-rollback-k consecutive bad steps roll "
                   "back to the last-good state with an LR backoff")
    p.add_argument("--ft-rollback-k", default=d.ft_rollback_k, type=int,
                   dest="ft_rollback_k", metavar="K",
                   help="consecutive non-finite steps before the guard "
                   "rolls back (default 3)")
    p.add_argument("--ft-check-every", default=d.ft_check_every, type=int,
                   dest="ft_check_every", metavar="N",
                   help="drain the guard's buffered non-finite flags every "
                   "N steps — one amortized host sync, never per step "
                   "(default 10)")
    p.add_argument("--ft-lr-backoff", default=d.ft_lr_backoff, type=float,
                   dest="ft_lr_backoff", metavar="F",
                   help="multiply the LR by this factor at each rollback "
                   "(default 0.5)")
    p.add_argument("--preempt-signals", default=d.preempt_signals, type=str,
                   dest="preempt_signals", metavar="SIGS",
                   help="comma-separated signals the preemption guard traps "
                   "(default 'term'; add 'int' for interactive Ctrl-C runs, "
                   "e.g. 'term,int')")
    p.add_argument("--elastic", action="store_true", dest="elastic",
                   help="elastic training (ft/elastic.py): on rank loss "
                   "re-mesh to the survivors and continue from the "
                   "last-good snapshot; on rank join re-shard and re-admit "
                   "— every shrink/grow is a 'remesh' ft_event the goodput "
                   "ledger books")
    p.add_argument("--min-ranks", default=d.min_ranks, type=int,
                   dest="min_ranks", metavar="N",
                   help="elastic shrink floor: refuse membership changes "
                   "that would take the data axis below N ranks "
                   "(default 1)")
    p.add_argument("--rescale-lr", default=d.rescale_lr,
                   choices=("none", "linear", "sqrt"), dest="rescale_lr",
                   help="LR/global-batch rule across an elastic world "
                   "change: none = hold the global batch constant, LR "
                   "untouched (parity default); linear/sqrt = hold the "
                   "per-rank batch constant and scale the LR by (new/old) "
                   "or sqrt(new/old)")
    p.add_argument("--epoch-csv", default=d.epoch_csv, type=str,
                   help="append [timestamp, epoch_seconds] rows to this CSV")
    p.add_argument("--profile-dir", default=d.profile_dir, type=str,
                   help="write an XPlane/TensorBoard profiler trace of the "
                   "first trained epoch of this run to this directory "
                   "(narrow the window with --profile-epochs/--profile-steps)")
    p.add_argument("--profile-epochs", default=d.profile_epochs, type=str,
                   dest="profile_epochs", metavar="E[:F]",
                   help="epoch window to trace under --profile-dir "
                   "('2' or '2:4'); default: the first trained epoch")
    p.add_argument("--profile-steps", default=d.profile_steps, type=str,
                   dest="profile_steps", metavar="I[:J]",
                   help="in-epoch step window narrowing the trace to steady "
                   "state ('10' or '10:20'); default: whole epoch")
    p.add_argument("--metrics-jsonl", default=d.metrics_jsonl, type=str,
                   dest="metrics_jsonl", metavar="PATH",
                   help="append one structured JSON record per train step "
                   "(wall time, step-time EMA/p50/p95/max, throughput, "
                   "loss, lr, in-graph grad/param norms) to this file; "
                   "summarize with scripts/obs_report.py")
    p.add_argument("--hb-dir", default=d.hb_dir, type=str, dest="hb_dir",
                   metavar="DIR",
                   help="shared heartbeat directory: each mesh process "
                   "appends {pid, step, t} beats; scripts/obs_report.py "
                   "flags stragglers by step lag / beat age")
    p.add_argument("--hb-interval", default=d.hb_interval_s, type=float,
                   dest="hb_interval_s", metavar="SEC",
                   help="minimum seconds between heartbeats (default 5)")
    p.add_argument("--mfu", action="store_true",
                   help="report per-step MFU/HFU in the metrics JSONL: the "
                   "analytic FLOPs model for the arch (obs/flops.py, "
                   "cross-checked against XLA cost_analysis) over the "
                   "chip's peak; supported for the ResNet and ViT families")
    p.add_argument("--goodput", action="store_true",
                   help="track the goodput/badput ledger live (nan-skips, "
                   "rollback discards, preemption gaps, recompiles, "
                   "stalls) and print the summary at end of fit; the "
                   "post-hoc equivalent is scripts/obs_report.py over "
                   "--metrics-jsonl")
    p.add_argument("--watch-recompiles", action="store_true",
                   dest="watch_recompiles",
                   help="recompile watchdog (obs/watchdog.py): count XLA "
                   "compilations per jitted step-fn via jax.monitoring and "
                   "flag any recompilation after warmup as an anomaly "
                   "event in the metrics JSONL")
    p.add_argument("--comm-ledger", default=d.comm_ledger, type=str,
                   dest="comm_ledger", metavar="PATH",
                   help="write the step's itemized communication ledger "
                   "(per-collective bytes, replica-group fan-out, scope "
                   "attribution; obs/comms.py) to PATH and stamp "
                   "model_comm_bytes/comm_wire_bytes/collective_count "
                   "into each metrics record; costs one extra AOT compile "
                   "of the step")
    p.add_argument("--mem-ledger", default=d.mem_ledger, type=str,
                   dest="mem_ledger", metavar="PATH",
                   help="write the step's static HBM memory ledger "
                   "(per-instruction live-range watermark, top buffers at "
                   "the high-water mark, params/opt-state/activations/"
                   "collective breakdown; obs/memory.py) to PATH and stamp "
                   "mem_peak_bytes into each metrics record; rides the "
                   "--comm-ledger AOT lowering, so together they cost one "
                   "extra compile, not two")
    p.add_argument("--lowering-cache", default=d.lowering_cache, type=str,
                   dest="lowering_cache", metavar="DIR",
                   help="persist the ledger AOT lowering's artifacts "
                   "(<step>.hlo + <step>.json: HLO text, mesh shape, "
                   "measured peak, arg classes; analysis/lowering.py "
                   "layout) under DIR for post-hoc text-only re-analysis")
    p.add_argument("--flight-rec", default=d.flight_rec, type=str,
                   dest="flight_rec", metavar="DIR",
                   help="flight recorder (obs/flightrec.py): keep a "
                   "bounded in-memory ring of step/collective/ft events "
                   "(~zero hot-path cost) and dump it to DIR/"
                   "flightrec_rank<k>.json on any death path — signal, "
                   "rollback, checkpoint corruption, unhandled exception, "
                   "or the collective-hang watchdog; merge dumps with "
                   "scripts/postmortem.py")
    p.add_argument("--hang-timeout", default=d.hang_timeout, type=float,
                   dest="hang_timeout", metavar="SEC",
                   help="hang-watchdog floor: flag a step exceeding "
                   "max(SEC, 4×p95 of completed steps), emit a `hang` "
                   "ft_event with the last-entered collective, and dump "
                   "the flight ring pre-mortem (needs --flight-rec)")
    p.add_argument("--metrics-port", default=d.metrics_port, type=int,
                   dest="metrics_port", metavar="PORT",
                   help="serve live Prometheus metrics on PORT + rank "
                   "(one daemon thread per rank, latest drained record; "
                   "0 disables; watch the fleet with scripts/obs_live.py)")
    p.add_argument("--alerts", default=d.alerts, type=str, dest="alerts",
                   metavar="RULES",
                   help="declarative alert rules: a JSON rules file or "
                   "'default' for the built-in set (obs/alerts.py); "
                   "firing alerts are booked as `alert` ft_events in the "
                   "metrics JSONL and exported to /metrics")
    p.add_argument("--step-attr", action="store_true",
                   default=d.step_attr, dest="step_attr",
                   help="exact per-step wall-time attribution "
                   "(obs/stepattr.py): stamp attr_* fields — compute / "
                   "exposed_comm / host_sync / data_wait / other, summing "
                   "to step_time exactly — into every metrics record; "
                   "analyze with scripts/obs_roofline.py")
    p.add_argument("--telemetry-csv", default=d.telemetry_csv, type=str,
                   help="sample device memory stats to this CSV every 500ms "
                   "during training (statistics.sh-in-process)")
    p.add_argument("--stem", default=d.stem,
                   choices=("conv7", "space_to_depth"),
                   help="ResNet stem: torchvision conv7 or the numerically "
                   "identical space-to-depth packing (TPU MXU-friendly)")
    p.add_argument("--fused-ce", default=d.fused_ce_chunks, type=int,
                   metavar="CHUNKS", dest="fused_ce_chunks",
                   help="LM family: fused tied-head+CE loss in CHUNKS row "
                   "blocks (ops/fused_ce.py); 0 = unfused logits head")
    p.add_argument("--fused-ce-mode", default=d.fused_ce_mode,
                   choices=("auto", "replicated", "dp", "tp"),
                   dest="fused_ce_mode",
                   help="fused-CE sharding variant: dp keeps the backward's "
                   "dE accumulator vocab-row-sharded over the data axis; tp "
                   "consumes the Megatron vocab-sharded embedding directly; "
                   "auto picks from the mesh + param specs")
    p.add_argument("--sync-bn", action="store_true", dest="sync_bn",
                   help="cross-replica BatchNorm for the explicit-"
                   "collectives step: psum the batch moments over the data "
                   "axis (global-batch statistics, = torch SyncBatchNorm); "
                   "GSPMD runs already have this semantics implicitly")
    return p


def parse_config(argv=None, description: str = "TPU ImageNet Training") -> Config:
    args = build_parser(description).parse_args(argv)
    return Config(**{k: v for k, v in vars(args).items()})

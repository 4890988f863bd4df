"""Epoch driver: the reference's ``main_worker`` / ``train`` / ``validate``
harness (reference distributed.py:129-324) rebuilt around compiled SPMD steps.

One Trainer serves every recipe; recipes differ only in driver-level config
(mesh construction, precision, explicit-vs-GSPMD collectives, multi-host
bootstrap) — the TPU-native collapse of the reference's six-script mechanism
diversity (SURVEY.md §7.1).
"""

from __future__ import annotations

import os
import time
from contextlib import nullcontext
from typing import Iterator, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from pytorch_distributed_tpu import models
from pytorch_distributed_tpu.data import (
    DataLoader,
    DeviceFeeder,
    DistributedShardSampler,
    ImageFolder,
    SyntheticImageDataset,
)
from pytorch_distributed_tpu.data.transforms import eval_transform, train_transform
from pytorch_distributed_tpu.obs import (
    HeartbeatWriter,
    MetricsLogger,
    ProfileWindow,
    sample_process_memory,
    scope,
    span,
)
from pytorch_distributed_tpu.obs.trace import dump_beside_capture
from pytorch_distributed_tpu.parallel import DistContext, data_parallel_mesh
from pytorch_distributed_tpu.train.checkpoint import load_checkpoint, save_checkpoint
from pytorch_distributed_tpu.train.config import Config
from pytorch_distributed_tpu.train.lr import cosine_lr, step_decay_lr
from pytorch_distributed_tpu.train.meters import AverageMeter, ProgressMeter, StepMeters
from pytorch_distributed_tpu.train.optim import sgd_init
from pytorch_distributed_tpu.train.state import TrainState
from pytorch_distributed_tpu.train.steps import (
    make_eval_step,
    make_train_step,
    state_shardings,
)
from pytorch_distributed_tpu.utils import EpochCSVLogger


class Trainer:
    def __init__(
        self,
        cfg: Config,
        mesh: Optional[Mesh] = None,
        ctx: Optional[DistContext] = None,
        explicit_collectives: bool = False,
        wire_dtype=None,
        grad_compress: Optional[str] = None,
        zero: Optional[str] = None,
        data_axis: str = "data",
        tx=None,
        preempt=None,
        chaos=None,
    ):
        """``tx``: optional optax GradientTransformation replacing the
        default torch-parity SGD (see train/steps.py docstring).

        ``grad_compress``: gradient wire format for the DP sync
        (none|bf16|int8|fp8, ops/qcomm.py); falls back to
        ``cfg.grad_compress``.  The legacy ``wire_dtype`` argument is the
        deprecated bf16-mode alias.

        ``zero``: ``none|wus`` weight-update sharding (parallel/zero.py);
        falls back to ``cfg.zero``.  Under ``wus`` the optimizer state is
        sharded 1/N over the data axis — stacked chunks on the explicit
        step, ``fsdp_specs`` shardings under GSPMD — and checkpoints keep
        storing the param-shaped momentum, so runs restore across modes.

        ``preempt``: optional ``utils.preempt.PreemptionGuard`` (already
        installed) polled between steps; ``fit()`` installs a guard for
        ``cfg.preempt_signals`` (default SIGTERM) when none is given.

        ``chaos``: optional ``ft.chaos`` injector schedule called once per
        train step (fault-injection drills and the survival tests)."""
        self.cfg = cfg
        self.preempt = preempt
        self.chaos = chaos
        self._agree = None  # built lazily (PreemptionAgreement over the mesh)
        self.ctx = ctx or DistContext(
            jax.process_index(), jax.process_count(), None
        )
        self.mesh = mesh if mesh is not None else data_parallel_mesh()
        self.data_axis = data_axis

        # Global batch divided across processes (reference distributed.py:146
        # divides by nprocs; we divide by process count — device-level split
        # happens in the sharded feeder, so per-chip batch is global/chips).
        cfg.nprocs = self.ctx.process_count
        if cfg.batch_size % max(1, self.ctx.process_count):
            raise ValueError(
                f"global batch {cfg.batch_size} not divisible by "
                f"{self.ctx.process_count} processes"
            )
        self.local_batch = cfg.batch_size // max(1, self.ctx.process_count)

        # Data first: ImageFolder infers num_classes, which sizes the head.
        self._build_data()

        dtype = jnp.bfloat16 if cfg.precision == "bf16" else jnp.float32
        # --stem is a ResNet-family knob; only forwarded when non-default.
        extra = {} if cfg.stem == "conv7" else {"stem": cfg.stem}
        if extra and getattr(
            models._REGISTRY.get(cfg.arch), "func", None
        ) is not models.ResNet:
            raise ValueError(
                "--stem only applies to the ResNet family; "
                f"arch {cfg.arch!r} has no such variant"
            )
        if getattr(cfg, "sync_bn", False) and explicit_collectives:
            # Cross-replica BN moments inside the shard_map step (torch
            # SyncBatchNorm ≙, model-agnostic like torch's): every BN
            # model family threads bn_axis_name into its norm layers.
            # GSPMD already has global-batch semantics, so the flag is a
            # documented no-op there.
            extra["bn_axis_name"] = data_axis
            # Explicit capability check instead of catching the
            # CPython-wording-dependent rejected-kwarg TypeError: a
            # BN-carrying model class declares bn_axis_name as a dataclass
            # field (flax modules are dataclasses), so its absence IS the
            # "no BatchNorm" signal — robust to constructor wrappers and
            # message-wording changes.  (Plain VGG keeps its own in-class
            # check: the class carries the field for the *_bn variants but
            # a BN-free cfg must still refuse at init.)
            import dataclasses as _dc

            ctor = (models._REGISTRY.get(cfg.arch)
                    or models._LM_REGISTRY.get(cfg.arch))
            cls = getattr(ctor, "func", ctor)
            fields = ({f.name for f in _dc.fields(cls)}
                      if _dc.is_dataclass(cls) else set())
            if "bn_axis_name" not in fields:
                raise ValueError(
                    f"--sync-bn: arch {cfg.arch!r} has no BatchNorm layers "
                    f"to synchronize (no bn_axis_name knob)")
        self.model = models.create_model(
            cfg.arch, num_classes=cfg.num_classes, dtype=dtype, **extra
        )

        # Resolve the gradient wire format once (kwarg > cfg; wire_dtype is
        # the deprecated bf16 alias) — the mode decides the error-feedback
        # residual layout carried in TrainState.
        from pytorch_distributed_tpu.ops import qcomm

        gc = grad_compress if grad_compress is not None else cfg.grad_compress
        self.grad_compress, self._grad_cast = qcomm.resolve_mode(
            gc, wire_dtype)

        # Weight-update sharding (kwarg > cfg, like grad_compress) — the
        # mode decides the optimizer-state layout carried in TrainState.
        from pytorch_distributed_tpu.parallel import zero as zero_lib

        self.zero = zero_lib.resolve_zero(
            zero if zero is not None else getattr(cfg, "zero", None))
        if self.zero == "wus" and tx is not None:
            raise ValueError(
                "--zero wus implements the torch-parity SGD on 1/N shards; "
                "an optax tx cannot be chunked — drop one of them")

        seed = cfg.seed if cfg.seed is not None else 0
        # Stashed for _build_for_mesh: an elastic re-mesh rebuilds the
        # jitted steps and feeder against the survivor set.
        self._explicit = explicit_collectives
        self._tx = tx
        self._seed = seed
        rng = jax.random.PRNGKey(seed)
        sample = jnp.zeros((1, cfg.image_size, cfg.image_size, 3), jnp.float32)
        variables = self.model.init(rng, sample, train=False)
        n_data = dict(self.mesh.shape)[self.data_axis]
        self._mom_sharding = None   # non-replicated momentum layout (wus)
        if self.zero == "wus" and explicit_collectives:
            from jax.sharding import NamedSharding, PartitionSpec

            opt0 = zero_lib.init_wus_momentum(
                variables["params"], n_data,
                quantized=self.grad_compress in qcomm.QUANTIZED_MODES)
            self._mom_sharding = NamedSharding(
                self.mesh, PartitionSpec(self.data_axis))
            opt0 = jax.device_put(opt0, self._mom_sharding)
        elif self.zero == "wus":
            from jax.sharding import NamedSharding

            opt0 = sgd_init(variables["params"])
            self._mom_sharding = jax.tree_util.tree_map(
                lambda s: NamedSharding(self.mesh, s),
                zero_lib.zero_momentum_specs(
                    variables["params"], self.mesh, data_axis=self.data_axis))
            opt0 = jax.device_put(opt0, self._mom_sharding)
        else:
            opt0 = tx.init(variables["params"]) if tx is not None else \
                sgd_init(variables["params"])
        residual = qcomm.init_residual(
            variables["params"], self.grad_compress,
            explicit=explicit_collectives,
            n_data=n_data)
        self.state = TrainState.create(variables, opt0, residual=residual)
        del variables

        if cfg.pretrained:
            self._load_pretrained()

        # Divergence guard + last-good snapshot (ft/): policy over the
        # in-graph nonfinite flag the step emits under --nan-guard.
        self.ft_guard = None
        self._keeper = None
        if getattr(cfg, "nan_guard", False):
            from pytorch_distributed_tpu.ft import DivergenceGuard, StateKeeper

            self._keeper = StateKeeper()
            # obs wired below (constructed later in __init__); attached then.
            self.ft_guard = DivergenceGuard(
                rollback_k=cfg.ft_rollback_k,
                check_every=cfg.ft_check_every,
                lr_backoff=cfg.ft_lr_backoff)

        self.best_acc1 = 0.0
        self._resume_step = 0    # step-in-epoch offset for the first epoch
        self._resume_global = 0
        if cfg.resume:
            self.state, meta = load_checkpoint(cfg.resume, self.state)
            self.best_acc1 = float(meta["best_acc1"])
            ft = meta["ft"]
            self._resume_step = int(ft["step"])
            self._resume_global = int(ft["global_step"])
            if self.ft_guard is not None:
                self.ft_guard.lr_scale = float(ft["lr_scale"])
            if self._resume_step > 0 and int(ft["sampler_seed"]) != (
                    cfg.seed if cfg.seed is not None else 0):
                import warnings

                warnings.warn(
                    f"resuming mid-epoch with --seed "
                    f"{cfg.seed if cfg.seed is not None else 0} but the "
                    f"checkpoint's sampler ran with seed "
                    f"{int(ft['sampler_seed'])}: the shuffle permutation "
                    f"differs, so the resumed epoch will not be "
                    f"sample-exact", stacklevel=2)
            if cfg.start_epoch == 0:
                # Mid-epoch checkpoint (ft step > 0): rerun the SAME epoch
                # from that step; epoch-boundary checkpoint: next epoch.
                cfg.start_epoch = int(meta["epoch"]) + (
                    0 if self._resume_step > 0 else 1)
            print(
                f"=> resumed {meta['arch']} from '{cfg.resume}' "
                f"(epoch {meta['epoch']}, step {self._resume_step}, "
                f"best_acc1 {self.best_acc1:.3f})"
            )

        # Validate accumulation settings BEFORE building the step — an invalid
        # accum_steps inside make_train_step would only surface as a confusing
        # trace-time reshape error (round-1 advisor finding).
        if cfg.accum_steps < 1:
            raise ValueError(f"--accum-steps must be >= 1, got {cfg.accum_steps}")
        if cfg.accum_steps > 1:
            # Each strided microbatch must still cover every data-axis shard
            # evenly, or XLA reshards the input on every scan iteration.
            shards = dict(self.mesh.shape)[self.data_axis]
            micro_global = cfg.batch_size // cfg.accum_steps
            if cfg.batch_size % cfg.accum_steps or micro_global % shards:
                raise ValueError(
                    f"global batch {cfg.batch_size} / --accum-steps "
                    f"{cfg.accum_steps} must be a whole multiple of the "
                    f"'{self.data_axis}' mesh axis ({shards} shards)"
                )
        # Everything mesh-shape-dependent (jitted steps, feeder, the
        # momentum sharding, topology-keyed caches) builds in one place so
        # an elastic re-mesh can rebuild it against the survivor set.
        self._build_for_mesh(self.mesh)
        # One observability entry point (obs/): the epoch CSV registers as
        # an epoch sink, a --telemetry-csv sampler registers in fit(), and
        # per-step structured records land in --metrics-jsonl.
        self.csv = EpochCSVLogger(cfg.epoch_csv)
        self.obs = MetricsLogger(cfg.metrics_jsonl,
                                 process_index=self.ctx.process_index)
        self.obs.register(self.csv)
        self.hb = (HeartbeatWriter(cfg.hb_dir, self.ctx.process_index,
                                   interval_s=cfg.hb_interval_s)
                   if cfg.hb_dir else None)
        if self.ft_guard is not None:
            self.ft_guard.obs = self.obs  # ft_event records → metrics JSONL
        # Efficiency accounting (obs/): per-step MFU/HFU from the analytic
        # FLOPs model, the live goodput ledger, and the recompile watchdog.
        self._mfu = None
        self._mfu_on = bool(getattr(cfg, "mfu", False))
        if self._mfu_on:
            self._build_mfu()
        self._goodput = None
        if getattr(cfg, "goodput", False):
            from pytorch_distributed_tpu.obs.goodput import GoodputTracker

            self._goodput = self.obs.register(GoodputTracker())
        self.watchdog = None
        if getattr(cfg, "watch_recompiles", False):
            from pytorch_distributed_tpu.obs.watchdog import (
                RecompileWatchdog,
            )

            self.watchdog = RecompileWatchdog(obs=self.obs).install()
        # Flight recorder (obs/flightrec.py): bounded per-rank event ring
        # + collective-hang watchdog, dumped on any death path; the
        # signal-dump chain and the watchdog thread start in fit().
        self.flight = None
        self._hang_wd = None
        if getattr(cfg, "flight_rec", None):
            from pytorch_distributed_tpu.obs.flightrec import (
                FlightRecorder,
                HangWatchdog,
                attach_to_metrics,
            )

            self.flight = FlightRecorder(cfg.flight_rec,
                                         rank=self.ctx.process_index)
            self._hang_wd = HangWatchdog(
                self.flight, obs=self.obs,
                timeout=float(getattr(cfg, "hang_timeout", 30.0)))
            # Every ft_event the metrics logger sees (skip/rollback/
            # preempt/remesh, incl. DivergenceGuard's) lands in the ring.
            attach_to_metrics(self.flight, self.obs)
        # Live telemetry plane (obs/export.py + obs/alerts.py): the
        # exporter and the rule engine are both flush-time sinks on the
        # same logger — zero additions to the hot loop.  The exporter is
        # an owned sink (started here, stopped at obs.close()); rank k
        # serves metrics_port + k.
        self._exporter = None
        if int(getattr(cfg, "metrics_port", 0) or 0) > 0:
            from pytorch_distributed_tpu.obs.export import MetricsExporter

            self._exporter = MetricsExporter(
                int(cfg.metrics_port) + self.ctx.process_index,
                rank=self.ctx.process_index)
            self.obs.register(self._exporter)        # lifecycle (start/stop)
            self.obs.register(self._exporter.update)  # per-record sink
        self.alerts = None
        if getattr(cfg, "alerts", None):
            from pytorch_distributed_tpu.obs.alerts import (
                AlertEngine,
                default_rules,
                load_rules,
            )

            rules = (default_rules() if cfg.alerts == "default"
                     else load_rules(cfg.alerts))
            self.alerts = AlertEngine(
                rules, emit=self._emit_alert,
                process_index=self.ctx.process_index)
            self.obs.register(self.alerts)
            if self._exporter is not None:
                self._exporter.engine = self.alerts  # ptd_alert_firing
        # Exact step attribution (obs/stepattr.py, --step-attr): three
        # perf_counter wall windows per step + one explicit block on the
        # step outputs, closing step_time == compute + exposed_comm +
        # host_sync + data_wait + other exactly.  The device-window split
        # starts as a ledger estimate and upgrades to the comm ledger's
        # wire bytes when --comm-ledger runs (same lowering, no extra
        # compile); the static phase roofline books once as a
        # `stepattr_phases` ft_event.
        self.stepattr = None
        self._stepattr_phases_booked = False
        if getattr(cfg, "step_attr", False):
            from pytorch_distributed_tpu.obs.flops import chip_link_bytes
            from pytorch_distributed_tpu.obs.stepattr import StepAttr

            kind = getattr(self.mesh.devices.flat[0], "device_kind", "")
            self.stepattr = StepAttr(link_bytes_per_s=chip_link_bytes(kind))
        # Communication + memory ledgers (obs/comms.py, obs/memory.py):
        # emitted lazily on the first train batch (real shardings in
        # hand), opt-in because the AOT lowering does not share the jit
        # call cache — one extra compile shared by both receipts.
        self._comm_fields: Optional[dict] = None
        # Dominant ledger collective (kind/bytes/name) labelling the flight
        # ring's coll_enter events; None until a ledger lowering runs.
        self._flight_coll: Optional[dict] = None
        # Monotonic logged-train-step counter; a resume restores it so the
        # metrics JSONL step axis continues instead of restarting at 0.
        self._global_step = self._resume_global

        # ---- elastic membership (ft/elastic.py) ----
        from pytorch_distributed_tpu.ft import elastic as elastic_lib

        self.rescale_lr_rule = str(getattr(cfg, "rescale_lr", "none") or "none")
        if self.rescale_lr_rule not in elastic_lib.RESCALE_RULES:
            raise ValueError(
                f"--rescale-lr must be one of {elastic_lib.RESCALE_RULES}, "
                f"got {self.rescale_lr_rule!r}")
        self._elastic_lr_scale = 1.0
        self._membership_epoch = 0
        self.elastic = elastic_lib.elastic_controller_from_config(
            cfg, dict(self.mesh.shape)[self.data_axis])
        if self.elastic is not None and self._keeper is None:
            # Re-meshing re-shards from the same last-good host snapshot
            # the divergence guard rolls back to.
            from pytorch_distributed_tpu.ft import StateKeeper

            self._keeper = StateKeeper()
        if self.hb is not None:
            self.hb.set_membership(dict(self.mesh.shape)[self.data_axis],
                                   self._membership_epoch)
        if self.flight is not None:
            self.flight.set_membership(
                dict(self.mesh.shape)[self.data_axis],
                self._membership_epoch)

    def _emit_alert(self, **fields) -> None:
        """AlertEngine emit hook: book a firing as an ``alert`` ft_event
        in the same JSONL, so goodput/postmortem/obs_report fold it (and
        the flight ring records it via attach_to_metrics)."""
        self.obs.log_event("alert", **fields)

    def _build_for_mesh(self, mesh: Mesh) -> None:
        """Build (or rebuild) every mesh-shape-dependent piece against
        ``mesh``: the momentum sharding, jitted train/eval steps, the
        device feeder, and the topology-keyed caches (preemption
        agreement, comm-ledger fields).  Called once from ``__init__`` and
        again on every elastic ``remesh`` — the mesh-shape-agnostic seam
        that decouples trainer construction from mesh shape."""
        from pytorch_distributed_tpu.ops import qcomm
        from pytorch_distributed_tpu.parallel import zero as zero_lib

        cfg = self.cfg
        self.mesh = mesh
        if self.zero == "wus" and self._explicit:
            from jax.sharding import NamedSharding, PartitionSpec

            self._mom_sharding = NamedSharding(
                mesh, PartitionSpec(self.data_axis))
        elif self.zero == "wus":
            from jax.sharding import NamedSharding

            self._mom_sharding = jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s),
                zero_lib.zero_momentum_specs(
                    self.state.params, mesh, data_axis=self.data_axis))
        else:
            self._mom_sharding = None
        self.train_step = make_train_step(
            self.model,
            mesh,
            momentum=cfg.momentum,
            weight_decay=cfg.weight_decay,
            data_axis=self.data_axis,
            wire_dtype=(self._grad_cast
                        if self.grad_compress == "bf16" else None),
            grad_compress=self.grad_compress,
            explicit_collectives=self._explicit,
            seed=self._seed,
            tx=self._tx,
            accum_steps=cfg.accum_steps,
            # In-graph grad/param norms only when a metrics sink consumes
            # them — the reductions lengthen compiles, so observability
            # costs nothing when off.
            log_norms=bool(cfg.metrics_jsonl),
            guard_nonfinite=bool(getattr(cfg, "nan_guard", False)),
            zero=self.zero,
            params=self.state.params,
            # Comm-overlap scheduler (parallel/overlap.py): bucketed
            # backward-overlapped grad sync on the explicit step;
            # make_train_step rejects bucketed-under-GSPMD loudly.
            overlap=getattr(cfg, "overlap", "none"),
            bucket_mb=float(getattr(cfg, "bucket_mb", 4.0)),
        )
        residual_sharded = (self._explicit
                            and self.grad_compress in qcomm.QUANTIZED_MODES)
        self.eval_step = make_eval_step(
            self.model, mesh, data_axis=self.data_axis,
            residual_sharded=residual_sharded,
            momentum_sharding=self._mom_sharding)
        # Commit the state to the steps' own layout before the first call.
        # An unplaced state has another abstract type than the placed one
        # the step returns, so the second call would trace and compile the
        # whole step again (on the chip ResNet-50's step compiled twice).
        self.state = jax.device_put(self.state, state_shardings(
            mesh, self.data_axis, residual_sharded, self._mom_sharding))
        self.feeder = DeviceFeeder(mesh, data_axis=self.data_axis)
        self._agree = None        # PreemptionAgreement holds the old mesh
        self._comm_fields = None  # ledger re-emits against the new mesh

    def _build_mfu(self) -> None:
        from pytorch_distributed_tpu.obs.flops import (
            MFUReporter,
            device_peak_flops,
            image_step_cost,
        )

        cfg = self.cfg
        cost = image_step_cost(cfg.arch, cfg.batch_size, cfg.image_size,
                               cfg.num_classes)
        dev = self.mesh.devices.flat[0]
        self._mfu = MFUReporter(cost, n_devices=self.mesh.devices.size,
                                peak_per_chip=device_peak_flops(dev))

    def remesh(self, new_world: int, refresh_snapshot: bool = True) -> int:
        """Re-mesh to ``new_world`` devices on the data axis: rebuild the
        mesh / jitted steps / feeder from the survivor set and re-shard the
        last-good ``StateKeeper`` snapshot onto the new topology.  Returns
        the global step to resume from (the snapshot's step).

        Unlike the LM path, the explicit-collectives layouts bake n_data
        into the state itself, so this is where the layout surgery
        happens: stacked ZeRO-WUS momentum chunks re-grid losslessly
        (flat-concat → truncate → re-chunk, ft/elastic.py) and stacked
        per-rank error-feedback residuals fold their sum into slot 0 —
        the total pending correction is preserved exactly.  Param-shaped
        leaves need no surgery; the jitted step's in_shardings place the
        host snapshot on the next call, exactly like ``_rollback``."""
        from pytorch_distributed_tpu.ft import elastic as elastic_lib
        from pytorch_distributed_tpu.ops import qcomm
        from pytorch_distributed_tpu.parallel import zero as zero_lib
        from pytorch_distributed_tpu.parallel.mesh import MeshSpec, build_mesh

        axes = tuple(self.mesh.axis_names)
        if axes != (self.data_axis,):
            raise ValueError(
                f"elastic re-mesh supports pure data-parallel meshes; "
                f"this trainer's mesh has axes {axes}")
        devs = jax.devices()
        if not 1 <= new_world <= len(devs):
            raise ValueError(
                f"new world {new_world} outside [1, {len(devs)}] devices")
        old_world = dict(self.mesh.shape)[self.data_axis]
        if self._keeper is None:
            from pytorch_distributed_tpu.ft import StateKeeper

            self._keeper = StateKeeper()
        if refresh_snapshot or not self._keeper.has_snapshot:
            self._keeper.update(self.state, self._global_step)
        host = self._keeper.restore()
        resume_global = int(self._keeper.step)
        if self.rescale_lr_rule != "none":
            new_batch = elastic_lib.rescale_batch(
                self.cfg.batch_size, old_world, new_world,
                self.rescale_lr_rule)
            self._elastic_lr_scale *= elastic_lib.rescale_lr(
                1.0, old_world, new_world, self.rescale_lr_rule)
            if new_batch != self.cfg.batch_size:
                # Per-rank batch held constant: loaders re-size (epoch
                # length changes take effect from the resume step).
                self.cfg.batch_size = new_batch
                self.local_batch = new_batch // max(
                    1, self.ctx.process_count)
                self._build_data()
        if self.cfg.batch_size % new_world:
            raise ValueError(
                f"global batch {self.cfg.batch_size} does not divide the "
                f"new data axis ({new_world} devices); pick --min-ranks / "
                "batch so every admissible world divides it")
        new_mesh = build_mesh(MeshSpec((self.data_axis,), (new_world,)),
                              devices=devs[:new_world])
        momentum = host.momentum
        if zero_lib.is_wus_momentum(momentum):
            momentum = elastic_lib.regrid_wus_momentum(
                momentum, host.params, new_world)
        residual = host.residual
        if (self._explicit and self.grad_compress in qcomm.QUANTIZED_MODES
                and residual):
            residual = elastic_lib.regrid_stacked_residual(residual,
                                                           new_world)
        self.state = TrainState(host.step, host.params, host.batch_stats,
                                momentum, residual)
        self._build_for_mesh(new_mesh)
        if self._mfu_on:
            self._build_mfu()  # n_devices (and maybe batch) changed
        self._membership_epoch += 1
        if self.hb is not None:
            self.hb.set_membership(new_world, self._membership_epoch)
        if self.flight is not None:
            self.flight.set_membership(new_world, self._membership_epoch)
        return resume_global

    def _apply_remesh(self, chg, epoch: int) -> int:
        """Act on a committed ``MembershipChange`` inside ``train_epoch``:
        log the ``remesh`` ft_event (goodput books the gap to the first
        step on the new mesh) and rebuild.  Returns the global resume
        step."""
        kind = chg.kind
        old_world = dict(self.mesh.shape)[self.data_axis]
        self.obs.log_event("remesh", step=self._global_step, change=kind,
                           old_world=chg.old.world, new_world=chg.new.world,
                           epoch=chg.new.epoch, reason=chg.reason,
                           rescale=self.rescale_lr_rule, train_epoch=epoch)
        resume = self.remesh(chg.new.world,
                             refresh_snapshot=(kind == "grow"))
        print(f"=> remesh ({kind}) at global step {self._global_step}: "
              f"world {old_world}->{chg.new.world}, epoch {chg.new.epoch}, "
              f"resuming at global step {resume} ({chg.reason})", flush=True)
        return resume

    def _load_pretrained(self) -> None:
        """``--pretrained`` parity (reference distributed.py:134-136 loads zoo
        weights).  TPU pods have no network egress, so weights come from a
        local directory: ``$PTD_TPU_PRETRAINED_DIR/<arch>.msgpack`` — any
        checkpoint this framework saved for the same arch."""
        d = os.environ.get("PTD_TPU_PRETRAINED_DIR", "pretrained")
        path = os.path.join(d, f"{self.cfg.arch}.msgpack")
        if not os.path.exists(path):
            raise FileNotFoundError(
                f"--pretrained: no weights at '{path}'; set "
                "PTD_TPU_PRETRAINED_DIR to a directory containing "
                f"{self.cfg.arch}.msgpack (a checkpoint saved by this framework)"
            )
        self.state, _ = load_checkpoint(path, self.state)
        print(f"=> using pre-trained model '{self.cfg.arch}' from '{path}'")

    # ------------------------------------------------------------------ data
    def _build_data(self) -> None:
        cfg = self.cfg
        world = self.ctx.process_count
        rank = self.ctx.process_index
        seed = cfg.seed if cfg.seed is not None else 0
        if cfg.synthetic:
            self.train_set = SyntheticImageDataset(
                length=cfg.synthetic_length,
                num_classes=cfg.num_classes,
                image_size=cfg.image_size,
                transform=None,
                seed=seed,
            )
            self.val_set = SyntheticImageDataset(
                length=max(cfg.synthetic_length // 10, world * 2),
                num_classes=cfg.num_classes,
                image_size=cfg.image_size,
                transform=None,
                seed=seed + 1,
            )
        elif cfg.wire == "native":
            # Full native host path: C++ JPEG decode + crop/resize, batch
            # flip host-side, uint8 across the wire, normalize on device.
            from pytorch_distributed_tpu.data.native import (
                jpeg_native_available,
            )

            if not jpeg_native_available():
                raise RuntimeError(
                    "--wire native needs the C++ data plane built against "
                    "libjpeg (g++ and libjpeg-dev); use --wire u8 or u8host "
                    "on this host"
                )
            self.train_set = ImageFolder(
                f"{cfg.data}/train", native_decode=True,
                image_size=cfg.image_size, native_augment=True,
            )
            self.val_set = ImageFolder(
                f"{cfg.data}/val", native_decode=True,
                image_size=cfg.image_size, native_augment=False,
            )
            cfg.num_classes = len(self.train_set.classes)
        else:
            if cfg.wire == "f32":
                ttf, vtf = train_transform(cfg.image_size), eval_transform(cfg.image_size)
            else:
                from pytorch_distributed_tpu.data.transforms import (
                    eval_transform_u8,
                    train_transform_u8,
                )

                ttf, vtf = train_transform_u8(cfg.image_size), eval_transform_u8(cfg.image_size)
            self.train_set = ImageFolder(f"{cfg.data}/train", transform=ttf)
            self.val_set = ImageFolder(f"{cfg.data}/val", transform=vtf)
            cfg.num_classes = len(self.train_set.classes)
        self.train_sampler = DistributedShardSampler(
            len(self.train_set), world, rank, shuffle=True, seed=seed
        )
        self.val_sampler = DistributedShardSampler(
            len(self.val_set), world, rank, shuffle=False, seed=seed
        )
        # drop_last on train: XLA needs static shapes, and a zero-padded
        # partial batch would pollute that batch's BatchNorm statistics.  The
        # torch reference trains on a smaller final batch instead (dynamic
        # shapes); with ImageNet-scale epochs the dropped tail is <1 batch.
        # Eval keeps padding + masks so metrics stay exact (SURVEY §7.4 it.3).
        # Synthetic datasets emit f32 directly; wire modes apply to the
        # ImageFolder (u8-transform) path.
        batch_mode = {"f32": "f32", "u8host": "u8_host", "u8": "u8_wire",
                      "native": "u8_wire"}[cfg.wire]
        if cfg.synthetic:
            batch_mode = "f32"
        self.train_loader = DataLoader(
            self.train_set,
            self.local_batch,
            sampler=self.train_sampler,
            num_workers=cfg.workers,
            drop_last=True,
            seed=seed,
            batch_mode=batch_mode,
            random_flip=batch_mode != "f32",
            worker_type=cfg.worker_type,
        )
        self.val_loader = DataLoader(
            self.val_set,
            self.local_batch,
            sampler=self.val_sampler,
            num_workers=cfg.workers,
            seed=seed,
            batch_mode=batch_mode,
            worker_type=cfg.worker_type,
        )

    def _wd_watch(self, label: str, step: Optional[int] = None):
        """Watchdog attribution context for a jitted call (inert when
        --watch-recompiles is off)."""
        if self.watchdog is not None:
            return self.watchdog.watch(label, step=step)
        import contextlib

        return contextlib.nullcontext()

    # ----------------------------------------------------------------- train
    def _ft_record(self, epoch: int, step_in_epoch: int) -> dict:
        return {
            "step": int(step_in_epoch),
            "global_step": int(self._global_step),
            "sampler_seed": int(self.train_sampler.seed),
            "sampler_epoch": int(epoch),
            "lr_scale": (self.ft_guard.lr_scale
                         if self.ft_guard is not None else 1.0),
        }

    def _save_step_checkpoint(self, epoch: int, step_in_epoch: int) -> None:
        """Mid-epoch (step-granular) checkpoint: --save-steps cadence and
        the preemption path.  ``step_in_epoch`` counts *completed* steps of
        ``epoch``; 0 completed steps degrade to the epoch-boundary form
        (previous epoch, step 0) so resume semantics stay uniform."""
        cfg = self.cfg
        if step_in_epoch > 0:
            e, ft = epoch, self._ft_record(epoch, step_in_epoch)
        else:
            e, ft = epoch - 1, self._ft_record(epoch - 1, 0)
        save_checkpoint(
            cfg.checkpoint_dir, self.state, e, cfg.arch, self.best_acc1,
            is_best=False, is_primary=self.ctx.is_primary,
            backend=cfg.ckpt_backend, metric=0.0, ft=ft,
        )
        if self.flight is not None:
            self.flight.event("checkpoint", self._global_step,
                              epoch=e, step_in_epoch=ft["step"])
        if self._keeper is not None:
            self._keeper.update(self.state, self._global_step)

    def _rollback(self, epoch: int, step_in_epoch: int) -> float:
        """Divergence recovery: restore the last-good host snapshot (the
        jitted step's in_shardings re-shard it next call) and back off the
        LR scale.  Returns the new scale for the caller's lr rebuild."""
        restored = None
        if self._keeper is not None and self._keeper.has_snapshot:
            self.state = self._keeper.restore()
            restored = self._keeper.step
        scale = self.ft_guard.note_rollback(self._global_step, restored)
        print(f"=> divergence rollback at epoch {epoch} step "
              f"{step_in_epoch}: restored state from global step "
              f"{restored}, lr scale now {scale:g}", flush=True)
        if self.flight is not None:
            # The rollback itself is forensic: snapshot the ring (the
            # `rollback` ft_event is already in it via attach_to_metrics).
            self.flight.dump("rollback")
        return scale

    def _emit_ledgers(self, batch, lr_arr) -> None:
        """AOT-compile the live train step once against the first batch's
        real shardings and itemize both opt-in receipts off that single
        lowering: the communication ledger (``--comm-ledger``) and the
        static HBM memory ledger (``--mem-ledger``).  The compile goes
        through ``analysis.lowering.aot_ledgers`` so it shares the
        process-wide compile counter (the tier-1 budget fence sees it)
        and, under ``--lowering-cache DIR``, persists the standard
        ``<step>.hlo``/``<step>.json`` artifact pair for post-hoc
        re-analysis; the cached metrics fields ride every subsequent
        ``log_step`` record."""
        from pytorch_distributed_tpu.analysis import lowering
        from pytorch_distributed_tpu.obs import comms

        cfg = self.cfg
        args = (self.state, batch, lr_arr)
        want_comm = bool(getattr(cfg, "comm_ledger", None))
        want_mem = bool(getattr(cfg, "mem_ledger", None))
        ledger, mled = lowering.aot_ledgers(
            self.train_step, args, step="train_step",
            mesh_shape=dict(self.mesh.shape), want_comm=want_comm,
            want_mem=want_mem,
            cache_dir=getattr(cfg, "lowering_cache", None))
        self._comm_fields = {}
        if ledger is not None:
            self._comm_fields.update(ledger.metrics_fields())
            if ledger.entries:
                top = max(ledger.entries, key=lambda e: e.wire_bytes)
                self._flight_coll = {"kind": top.kind, "bytes": top.bytes,
                                     "name": top.name}
            if self.ctx.process_index == 0:
                comms.write_ledgers(cfg.comm_ledger, [ledger])
                print(f"=> wrote comm ledger ({ledger.count} collectives, "
                      f"{ledger.total_bytes} B/step payload) to "
                      f"{cfg.comm_ledger}", flush=True)
        if mled is not None:
            from pytorch_distributed_tpu.obs import memory

            self._comm_fields.update(mled.metrics_fields())
            if self.ctx.process_index == 0:
                memory.write_ledgers(cfg.mem_ledger, [mled])
                print(f"=> wrote mem ledger (peak {mled.peak_bytes} B at "
                      f"instr {mled.peak_index}/{mled.n_instructions}) to "
                      f"{cfg.mem_ledger}", flush=True)

    def _book_stepattr_phases(self) -> None:
        """Feed the attribution recorder the comm ledger's measured wire
        bytes (when one ran — the estimate upgrade costs no compile) and
        book the static per-phase roofline ledger as a one-time
        ``stepattr_phases`` ft_event: per named_scope phase FLOPs/HBM
        bytes from the analytic StepCost plus the chip peaks, so the
        jax-free CLI never touches hardware tables."""
        if self.stepattr is None or self._stepattr_phases_booked:
            return
        self._stepattr_phases_booked = True
        from pytorch_distributed_tpu.obs import flops, stepattr

        cfg = self.cfg
        wire = float((self._comm_fields or {}).get("comm_wire_bytes", 0.0))
        if wire > 0:
            self.stepattr.set_comm_bytes(wire)
        try:
            cost = flops.image_step_cost(cfg.arch, cfg.batch_size,
                                         cfg.image_size, cfg.num_classes)
        except (KeyError, ValueError):
            return  # unregistered arch: attribution still runs, no roofline
        kind = getattr(self.mesh.devices.flat[0], "device_kind", "")
        prof = stepattr.phase_profile(
            cost.breakdown,
            stepattr.split_step_bytes(cost.bytes, cost.params),
            comm_bytes=wire,
            peak_flops=flops.chip_peak_flops(kind),
            hbm_bw=flops.chip_hbm_bw(kind),
            link_bw=flops.chip_link_bytes(kind),
            n_devices=self.mesh.devices.size)
        self.obs.log_event("stepattr_phases",
                           **stepattr.phase_event_fields(prof))

    def train_epoch(
        self, epoch: int, profiler: Optional[ProfileWindow] = None,
        start_step: int = 0,
    ) -> Tuple[int, bool]:
        """One epoch from ``start_step`` (0 except the first epoch of a
        mid-epoch resume).  Returns ``(completed_steps, preempted)`` so the
        epoch driver knows exactly where a preemption landed."""
        cfg = self.cfg
        if cfg.lr_schedule == "cosine":
            lr = cosine_lr(cfg.lr, epoch, cfg.epochs,
                           warmup_epochs=cfg.lr_warmup_epochs)
        elif cfg.lr_schedule == "step":
            lr = step_decay_lr(cfg.lr, epoch)
        else:  # argparse enforces choices; guard programmatic Configs too
            raise ValueError(
                f"unknown lr_schedule {cfg.lr_schedule!r}: "
                "expected 'step' or 'cosine'")
        meters = StepMeters(
            len(self.train_loader),
            [("loss", "Loss", ":.4e"), ("acc1", "Acc@1", ":6.2f"),
             ("acc5", "Acc@5", ":6.2f")],
            prefix=f"Epoch: [{epoch}]",
        )
        self.train_loader.set_epoch(epoch)
        self.val_sampler.set_epoch(epoch)
        scale = self.ft_guard.lr_scale if self.ft_guard is not None else 1.0
        lr_arr = jnp.float32(lr * scale * self._elastic_lr_scale)
        completed = start_step
        if self._keeper is not None and not self._keeper.has_snapshot:
            self._keeper.update(self.state, self._global_step)
        meters.restart_clock()
        # Global step this epoch's step 0 corresponds to — the anchor that
        # maps a StateKeeper (global-step) snapshot back to a step-in-epoch
        # when an elastic rewind lands mid-epoch.
        epoch_base = self._global_step - start_step
        epoch_len = len(self.train_loader)
        batch_iter = self.feeder(self.train_loader.iter_batches(start_step))
        i = start_step
        taken = 0  # batches taken from batch_iter: what the spans call id
        while i < epoch_len:
            # One `step` span an iteration (obs/trace.py); its children are
            # the feeder's `data_wait`, `dispatch` and the `host_sync`
            # drains, so its self time is the loop's own overhead: the
            # polls and hooks below, and waiting for the GIL between them.
            with span("step", id=taken):
                if profiler is not None:
                    profiler.step_begin(epoch, i)
                # Polled at print_freq cadence so the agreement collective (a
                # tiny any-rank-flagged all-reduce every rank runs at the same
                # step — signal skew across hosts must not break ranks at
                # different boundaries) stays off the per-step hot path.
                if (self.preempt is not None and i % cfg.print_freq == 0
                        and self._preempt_agreed()):
                    return completed, True
                if self.chaos is not None:
                    self.chaos.on_step(self, i)
                if self.elastic is not None:
                    # Membership epochs are committed by the coordinator and
                    # read by every rank at the same step — an agreed value,
                    # not a local probe (synclint would otherwise flag the
                    # re-mesh below as a rank-divergent collective path).
                    chg = self.elastic.poll(self._global_step)  # synclint: agreement
                    if chg is not None:
                        # Membership changed: rebuild against the survivor set
                        # and rewind to the snapshot step (the sampler's
                        # (seed, epoch) permutation regenerates the identical
                        # index stream, so replayed steps see the same data).
                        batch_iter.close()
                        resume_global = self._apply_remesh(chg, epoch)
                        self._global_step = resume_global
                        completed = i = max(0, resume_global - epoch_base)
                        epoch_len = len(self.train_loader)  # batch rescale
                        batch_iter = self.feeder(
                            self.train_loader.iter_batches(i))
                        taken = 0
                        lr_arr = jnp.float32(
                            lr * scale * self._elastic_lr_scale)
                        meters.restart_clock()
                        continue
                # Attribution windows (--step-attr): data_wait wraps batch
                # acquisition *and* the chaos on_batch hook, so an injected
                # loader delay (chaoskit drill slow-loader) lands in the
                # measured component by design.
                sa = self.stepattr
                _dw = sa.data_wait if sa is not None else nullcontext
                with _dw():
                    batch = next(batch_iter, None)
                if batch is None:
                    break
                taken += 1
                if self.chaos is not None:
                    with _dw():
                        batch = self.chaos.on_batch(i, batch)
                n = self.cfg.batch_size
                if ((getattr(cfg, "comm_ledger", None)
                        or getattr(cfg, "mem_ledger", None))
                        and self._comm_fields is None):
                    self._emit_ledgers(batch, lr_arr)
                if self.flight is not None:
                    # Ring: step window + collective region (labelled with the
                    # ledger's dominant entry when the AOT lowering ran) —
                    # two deque appends, no sync/I/O.
                    self.flight.step_begin(self._global_step)
                    fc = self._flight_coll or {}
                    self.flight.coll_enter(self._global_step,
                                           kind=fc.get("kind"),
                                           bytes=fc.get("bytes"),
                                           name=fc.get("name"))
                if self.chaos is not None:
                    self.chaos.on_collective(self, self._global_step)
                _dev = sa.device if sa is not None else nullcontext
                _hs = sa.host_sync if sa is not None else nullcontext
                with span("dispatch"), scope("train_step"), \
                        self._wd_watch("train_step", self._global_step), \
                        _dev():
                    self.state, metrics = self.train_step(self.state, batch, lr_arr)
                    if sa is not None:
                        # The step's blocking transfer: without it, async
                        # dispatch smears step N's device time into N+1's
                        # windows and the identity stops meaning anything.
                        # Only when --step-attr opted in; overhead fenced
                        # <2% p50 in RESULTS_stepattr.json.
                        jax.block_until_ready(metrics)  # shardlint: allow-sync
                if self.flight is not None:
                    self.flight.coll_exit(self._global_step)
                    self.flight.step_end(self._global_step)
                completed = i + 1
                # Unready device scalars: meters and the metrics logger convert
                # lazily, so no per-step host sync (SURVEY.md §7.4 item 1).
                with span("host_sync"), _hs():
                    dt = meters.update(metrics, n)
                extra = {"epoch": epoch}
                if self._mfu is not None:
                    extra.update(self._mfu.fields(dt))
                if self._comm_fields:
                    extra.update(self._comm_fields)
                if sa is not None:
                    extra.update(sa.fields(dt))
                # The lazy-flush scalar drain inside log_step accrues to the
                # *next* step's host_sync window (its dt covers this wall
                # time), keeping the identity aligned.
                with span("host_sync"), _hs():
                    self.obs.log_step(
                        self._global_step, step_time=dt, n_items=n, lr=lr,
                        scalars=dict(metrics),  # incl. norms when --metrics-jsonl
                        extra=extra,
                    )
                # booked after the first step's record so the event's
                # timestamp cannot widen the post-hoc goodput wall span back
                # across the step-0 compile
                if sa is not None and not self._stepattr_phases_booked:
                    self._book_stepattr_phases()
                if self.hb is not None:
                    self.hb.beat(self._global_step, step_time_ema=self.obs.ema,
                                 last_ft=self.obs.last_event_kind,
                                 mem_bytes=sample_process_memory(),
                                 data_wait_ms=(sa.data_wait_ema_ms
                                               if sa is not None else None))
                    if self.flight is not None:
                        self.flight.heartbeat(
                            {"step": self._global_step,
                             "last_ft": self.obs.last_event_kind})
                self._global_step += 1
                with span("host_sync"):
                    meters.maybe_display(i, cfg.print_freq)
                at_save = (cfg.save_steps > 0 and completed % cfg.save_steps == 0
                           and completed < len(self.train_loader))
                if self.ft_guard is not None:
                    # Flags buffer unconverted; drained every ft_check_every
                    # steps (one amortized host sync) — forced before a
                    # snapshot so it never races an undetected divergence.
                    rollback = self.ft_guard.observe(
                        self._global_step - 1, metrics.get("nonfinite"))
                    if at_save:
                        # The drained flag is the in-step all-reduced nonfinite
                        # count: every rank drains the identical value, so the
                        # rollback decision below is bulk-synchronous.
                        rollback = self.ft_guard.drain() or rollback  # synclint: agreement
                    if rollback:
                        lr_arr = jnp.float32(lr * self._rollback(epoch, i)
                                             * self._elastic_lr_scale)
                    # A flagged streak means the current state is suspect —
                    # don't refresh the last-good snapshot/checkpoint from it.
                    at_save = at_save and self.ft_guard.consecutive == 0
                if at_save:
                    self._save_step_checkpoint(epoch, completed)
                    meters.restart_clock()  # exclude checkpoint I/O from meter
                i += 1
        if self.ft_guard is not None and self.ft_guard.drain():  # synclint: agreement
            # Trailing flags (buffered past the last cadence point) must be
            # resolved before the epoch-end checkpoint can capture them.
            # Agreed: the flag drains an in-step all-reduced scalar.
            self._rollback(epoch, completed)
        return completed, False

    # ------------------------------------------------------------------ eval
    def validate(self) -> float:
        cfg = self.cfg
        batch_time = AverageMeter("Time", ":6.3f")
        losses = AverageMeter("Loss", ":.4e")
        top1 = AverageMeter("Acc@1", ":6.2f")
        top5 = AverageMeter("Acc@5", ":6.2f")
        progress = ProgressMeter(
            len(self.val_loader), [batch_time, losses, top1, top5], prefix="Test: "
        )
        totals = {"loss_sum": 0.0, "correct1": 0.0, "correct5": 0.0, "count": 0.0}
        end = time.time()
        for i, batch in enumerate(self.feeder(iter(self.val_loader))):
            with self._wd_watch("eval_step"):
                sums = self.eval_step(self.state, batch)
            c = float(sums["count"])
            if c > 0:
                losses.update(float(sums["loss_sum"]) / c, int(c))
                top1.update(float(sums["correct1"]) * 100.0 / c, int(c))
                top5.update(float(sums["correct5"]) * 100.0 / c, int(c))
            for k in totals:
                totals[k] += float(sums[k])
            batch_time.update(time.time() - end)
            end = time.time()
            if i % cfg.print_freq == 0:
                progress.display(i)
        count = max(totals["count"], 1.0)
        acc1 = totals["correct1"] * 100.0 / count
        acc5 = totals["correct5"] * 100.0 / count
        # Reference summary line (distributed.py:321-322).
        print(f" * Acc@1 {acc1:.3f} Acc@5 {acc5:.3f}", flush=True)
        return acc1

    # ------------------------------------------------------------------- fit
    def fit(self) -> float:
        """Train/eval driver with the unified observability surface (obs/):
        per-step meters + structured --metrics-jsonl records, per-epoch CSV,
        optional in-process device telemetry, per-process heartbeats
        (--hb-dir), and an optional XPlane profiler trace windowed by
        --profile-epochs/--profile-steps (the TPU-native upgrade of
        nvidia-smi sampling — open in TensorBoard's profile plugin)."""
        cfg = self.cfg
        if cfg.evaluate:
            return self.validate()
        if cfg.telemetry_csv and not getattr(self, "_telemetry_on", False):
            from pytorch_distributed_tpu.utils.telemetry import TelemetrySampler

            # Registered (not started ad hoc): obs.close() stops it.
            self.obs.register(TelemetrySampler(cfg.telemetry_csv))
            self._telemetry_on = True
        import threading

        from pytorch_distributed_tpu.utils.preempt import (
            PreemptionGuard,
            parse_signals,
        )

        # Default guard: cfg.preempt_signals (SIGTERM, the pod-reclaim
        # grace signal, by default; '--preempt-signals term,int' adds
        # Ctrl-C for interactive runs) triggers a checkpoint-and-exit at
        # the next safe boundary (SURVEY §5.3 upgrade).  Callers may pass
        # their own guard to Trainer().  Signal handlers are
        # main-thread-only in Python, so off-main-thread fit() callers
        # simply run unguarded unless they pass one in.
        installed = (self.preempt is None
                     and threading.current_thread() is threading.main_thread())
        if installed:
            self.preempt = PreemptionGuard(
                signals=parse_signals(cfg.preempt_signals)).install()
        if self.watchdog is not None:
            self.watchdog.install()  # idempotent (re-fit after a fit)
        if self._exporter is not None and not self._exporter.running:
            # A prior fit's obs.close() stopped the owned exporter;
            # re-register so this fit serves (and tears down) again.
            self.obs.register(self._exporter)
        # Flight recorder death paths: signal-dump chain (installed after
        # the preemption guard so the dump happens first, then chains to
        # it) + the collective-hang watchdog daemon.
        flight_sig = None
        if self.flight is not None:
            if threading.current_thread() is threading.main_thread():
                from pytorch_distributed_tpu.obs.flightrec import (
                    FlightSignalDump,
                )

                flight_sig = FlightSignalDump(
                    self.flight,
                    signals=parse_signals(cfg.preempt_signals)).install()
            if self._hang_wd is not None:
                self._hang_wd.start()
        try:
            return self._fit_epochs()
        except BaseException as e:
            if self.flight is not None:
                from pytorch_distributed_tpu.ft.integrity import (
                    CheckpointCorruptError,
                )

                self.flight.record("exception", self._global_step,
                                   error=type(e).__name__)
                self.flight.dump("checkpoint_corrupt"
                                 if isinstance(e, CheckpointCorruptError)
                                 else f"exception:{type(e).__name__}")
            raise
        finally:
            if installed:
                self.preempt.uninstall()
                self.preempt = None
            if self._hang_wd is not None:
                self._hang_wd.stop()
            if flight_sig is not None:
                flight_sig.uninstall()
            if self.watchdog is not None:
                self.watchdog.uninstall()
            if self.hb is not None:
                self.hb.close(max(0, self._global_step - 1),
                              step_time_ema=self.obs.ema,
                              last_ft=self.obs.last_event_kind,
                              mem_bytes=sample_process_memory(),
                              data_wait_ms=(self.stepattr.data_wait_ema_ms
                                            if self.stepattr is not None
                                            else None))
            if cfg.profile_dir:
                dump_beside_capture(cfg.profile_dir, self.train_step)
            self.obs.flush()
            if self._goodput is not None:
                print(f"=> {self._goodput.format_summary()}", flush=True)
            self.obs.close()  # flush JSONL, stop registered telemetry
            self._telemetry_on = False

    def _preempt_agreed(self) -> bool:
        """Cross-process 'any rank flagged?' — see utils/preempt.py.  Every
        rank must call this at the same loop boundary (it runs a collective
        on multi-process meshes)."""
        if self._agree is None:
            from pytorch_distributed_tpu.utils.preempt import (
                PreemptionAgreement,
            )

            self._agree = PreemptionAgreement(self.mesh, self.data_axis)
        return self._agree(self.preempt.triggered)

    def _fit_epochs(self) -> float:
        cfg = self.cfg
        profiler = ProfileWindow(cfg.profile_dir, epochs=cfg.profile_epochs,
                                 steps=cfg.profile_steps,
                                 start_epoch=cfg.start_epoch)
        for epoch in range(cfg.start_epoch, cfg.epochs):
            self.obs.epoch_start()
            profiler.epoch_begin(epoch)
            # Mid-epoch resume: the first epoch starts at the checkpointed
            # step offset — the sampler's (seed, epoch) permutation
            # regenerates the identical index stream, and the loader skips
            # the already-trained prefix by index arithmetic.
            start_step = (self._resume_step
                          if epoch == cfg.start_epoch else 0)
            completed, preempted = self.train_epoch(epoch, profiler,
                                                    start_step=start_step)
            jax.block_until_ready(self.state.params)
            if profiler.epoch_end():
                print(f"=> wrote profiler trace to '{cfg.profile_dir}'")
            if not preempted and (self.preempt is not None
                                  and self._preempt_agreed()):
                preempted = True  # signal landed between last poll and here
            if preempted:
                # Step-granular preemption checkpoint: the ft record pins
                # the exact completed step, so --resume continues from it —
                # no epoch rerun (the pre-FT behavior threw away up to a
                # whole epoch here).
                print(f"=> preemption signal: checkpointing at epoch "
                      f"{epoch} step {completed} and exiting", flush=True)
                self.obs.log_event("preempt", step=self._global_step,
                                   epoch=epoch, step_in_epoch=completed)
                self._save_step_checkpoint(epoch, completed)
                break
            acc1 = self.validate()
            elapsed = self.obs.epoch_end()  # drives the registered epoch CSV
            print(f"Epoch {epoch} took {elapsed:.1f}s", flush=True)
            is_best = acc1 > self.best_acc1
            self.best_acc1 = max(acc1, self.best_acc1)
            save_checkpoint(
                cfg.checkpoint_dir,
                self.state,
                epoch,
                cfg.arch,
                self.best_acc1,
                is_best,
                is_primary=self.ctx.is_primary,
                backend=cfg.ckpt_backend,
                metric=acc1,  # this epoch's own score (orbax best retention)
                ft=self._ft_record(epoch, 0),
            )
            if self._keeper is not None:
                self._keeper.update(self.state, self._global_step)
        if cfg.ckpt_backend == "orbax":
            from pytorch_distributed_tpu.train.checkpoint import (
                wait_for_async_saves,
            )

            wait_for_async_saves()
        return self.best_acc1

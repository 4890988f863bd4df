#!/usr/bin/env python
"""chip_smoke — the quickest proof that the system still starts on the chip.

Drives the main path once on a TPU, through the entry points a user types,
at the full width of the widest LM the repo has been run at (d_model 1024,
12 layers, 16 heads, vocabulary 32000) and of ResNet-50; random weights
from a seed, synthetic seeded data.  Three legs, one process each:

  image  recipes.tpu_native   ResNet-50, batch 256 at 224: five train
                              steps, the masked eval, one checkpoint
  lm     recipes.lm_pretrain  three steps at 4096 tokens, 4 rows a chip:
                              the length where ``auto`` picks the Pallas
                              flash kernel, forward and both backwards
  serve  scripts/serve_lm.py  16 requests through the paged engine

A chip belongs to one process at a time, so this parent never imports JAX:
it starts one child per leg (``chip_smoke.py --leg NAME``), one at a time,
and each child pins the platform to the TPU, so that a TPU that fails to
initialise raises instead of dropping to the CPU.  It runs unchanged on
one chip and on a four-chip host (the engine is a one-device program and
uses one chip there).

Everything is written under ``chip_smoke_out/`` (git-ignored); the compile
cache is where ``utils/compile_cache.py`` says.  The last line of stdout is
``{"ok": true, "device": {"platform", "kind", "count"}}`` and the exit code
0 only if every leg passed; otherwise the exit code is non-zero, the failed
leg's output is shown and no result line is printed.  Compile seconds and
wall seconds per leg are set-up figures, not metrics.
"""

from __future__ import annotations

import ctypes
import json
import math
import os
import shutil
import signal
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(ROOT, "chip_smoke_out")
DEADLINE_S = 1150.0  # the whole smoke, compilation included
LM = {"vocab": 32000, "d_model": 1024, "n_heads": 16, "n_layers": 12}
LM_ROWS_PER_CHIP = 4
LM_SEQ_LEN = 4096


# ------------------------------------------------------------ the three legs
# Each runs in its own child process, after ``require_tpu``.  A leg returns
# a dict of what it observed and raises if anything is wrong.

def _check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def leg_image(device: dict) -> dict:
    from pytorch_distributed_tpu.data.loader import DeviceFeeder
    from pytorch_distributed_tpu.recipes import tpu_native

    placements = _spy_placement(DeviceFeeder, "_put")
    metrics = os.path.join(OUT, "image_metrics.jsonl")
    best_acc1 = tpu_native.main([
        "--synthetic", "-a", "resnet50", "-b", "256", "--image-size", "224",
        "--epochs", "1", "--synthetic-length", "1280", "-p", "1",
        "--checkpoint-dir", os.path.join(OUT, "image_ckpt"),
        "--epoch-csv", os.path.join(OUT, "tpu_native.csv"),
        "--metrics-jsonl", metrics,
    ])
    with open(metrics) as f:
        losses = [r["loss"] for r in map(json.loads, f) if "loss" in r]
    _check(len(losses) == 5, f"expected 5 train steps, logged {len(losses)}")
    _check(all(math.isfinite(x) for x in losses), f"loss not finite: {losses}")
    _check(0.0 <= best_acc1 <= 100.0, f"masked eval Acc@1 {best_acc1}")
    ckpt = os.path.join(OUT, "image_ckpt", "checkpoint.msgpack")
    ckpt_mb = os.path.getsize(ckpt) / 1e6  # raises if it was not written
    _check(ckpt_mb > 100, f"checkpoint is only {ckpt_mb:.0f} MB")
    shutil.rmtree(os.path.dirname(ckpt))  # checked; 200 MB nobody reads
    with open(os.path.join(OUT, "tpu_native.csv")) as f:
        _check(len(f.read().splitlines()) >= 1, "no epoch CSV row")
    _check_placements(placements, device)
    return {"losses": [round(x, 4) for x in losses], "acc1": best_acc1,
            "checkpoint_mb": round(ckpt_mb), **_per_device_peaks()}


def leg_lm(device: dict) -> dict:
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_tpu.ops.flash_attention import flash_attention
    from pytorch_distributed_tpu.parallel.ring import dense_attention
    from pytorch_distributed_tpu.recipes import lm_pretrain
    from pytorch_distributed_tpu.train.lm import LMTrainer

    # The compiled Pallas kernels against XLA dense attention, at the
    # smoke's own sequence length and head width, values and gradients
    # (tests/test_flash_attention.py does this in interpret mode).
    shape = (1, LM_SEQ_LEN, 2, LM["d_model"] // LM["n_heads"])
    q, k, v = (jax.random.normal(key, shape, jnp.bfloat16)
               for key in jax.random.split(jax.random.PRNGKey(0), 3))

    def through(attend):
        def loss(q, k, v):
            out = attend(q, k, v).astype(jnp.float32)
            return (out * out).mean(), out
        (_, out), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
        return (out,) + grads

    flash = through(lambda q, k, v: flash_attention(q, k, v, True))
    dense = through(lambda q, k, v: dense_attention(q, k, v, causal=True))
    kernel_err = {}
    for name, a, b in zip(("out", "dq", "dk", "dv"), flash, dense):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        err = float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
        _check(err < 0.05, f"flash vs dense {name}: relative error {err}")
        kernel_err[name] = round(err, 5)

    placements = _spy_placement(LMTrainer, "_put_tokens")
    lowering = os.path.join(OUT, "lm_lowering")
    batch = LM_ROWS_PER_CHIP * device["count"]
    final_loss = lm_pretrain.main([
        "--d-model", str(LM["d_model"]), "--n-layers", str(LM["n_layers"]),
        "--n-heads", str(LM["n_heads"]), "--vocab", str(LM["vocab"]),
        "--seq-len", str(LM_SEQ_LEN), "-b", str(batch), "--steps", "3",
        "--no-eval", "-p", "1",
        "--mem-ledger", os.path.join(OUT, "lm_mem_ledger.json"),
        "--lowering-cache", lowering,
    ])
    # random weights: the loss starts near ln(vocab)
    _check(math.isfinite(final_loss)
           and 0.0 < final_loss < 2 * math.log(LM["vocab"]),
           f"final loss {final_loss}")
    # The compiled step really contains the kernels (on the CPU they are
    # interpreted into plain HLO), and is one program over every chip.
    with open(os.path.join(lowering, "lm_step.hlo")) as f:
        hlo = f.read()
    mosaic_calls = hlo.count('custom_call_target="tpu_custom_call"')
    _check(mosaic_calls == 3 * LM["n_layers"],
           f"{mosaic_calls} Mosaic calls in the compiled step, expected "
           f"{3 * LM['n_layers']} (forward, dq, dk/dv per layer)")
    _check(f"num_partitions={device['count']}" in hlo
           or device["count"] == 1, "step is not partitioned over the chips")
    _check_placements(placements, device)
    return {"batch": batch, "final_loss": round(final_loss, 4),
            "mosaic_calls": mosaic_calls, "flash_vs_dense": kernel_err,
            **_per_device_peaks()}


def leg_serve(device: dict) -> dict:
    sys.path.insert(0, os.path.join(ROOT, "scripts"))
    import serve_lm

    from pytorch_distributed_tpu.serving.loadgen import (
        LoadConfig,
        generate_load,
    )

    requests, max_new = 16, 32
    summary_path = os.path.join(OUT, "serve_summary.json")
    rc = serve_lm.main([
        "--vocab-size", str(LM["vocab"]), "--d-model", str(LM["d_model"]),
        "--n-heads", str(LM["n_heads"]), "--n-layers", str(LM["n_layers"]),
        "--max-batch", "8", "--kv-blocks", "1024", "--block-size", "16",
        "--blocks-per-seq", "64", "--chunk-size", "128",
        "--max-new-tokens", str(max_new), "--requests", str(requests),
        "--rate-rps", "8", "--summary-json", summary_path,
    ])
    _check(rc == 0, f"serve_lm returned {rc}: not every request completed")
    with open(summary_path) as f:
        summary = json.load(f)
    _check(summary["completed"] == requests, f"completed {summary}")
    _check(summary["recompile_anomalies"] == 0,
           f"{summary['recompile_anomalies']} recompiles after warm-up")
    # no end-of-sequence token: every request runs to its cap
    load = generate_load(LoadConfig(n_requests=requests, rate_rps=8.0,
                                    vocab_size=LM["vocab"], seed=0))
    want = sum(min(req.max_new_tokens, max_new) for _, req in load)
    _check(summary["tokens"] == want,
           f"{summary['tokens']} tokens generated, the load asks for {want}")
    return {"completed": summary["completed"], "tokens": summary["tokens"],
            "preemptions": summary["preemptions"]}


LEGS = {"image": leg_image, "lm": leg_lm, "serve": leg_serve}


# ------------------------------------------------ what the work was placed on

def _spy_placement(cls, method: str) -> list:
    """Record which devices hold a shard of every array ``cls.method``
    returns — the trainers' host-to-device placement seam."""
    import jax

    seen: list = []
    inner = getattr(cls, method)

    def spy(self, *args, **kwargs):
        out = inner(self, *args, **kwargs)
        for leaf in jax.tree_util.tree_leaves(out):
            seen.append(frozenset(
                s.device.id for s in leaf.addressable_shards))
        return out

    setattr(cls, method, spy)
    return seen


def _check_placements(placements: list, device: dict) -> None:
    import jax

    everywhere = frozenset(d.id for d in jax.devices())
    _check(bool(placements), "no batch was placed")
    _check(all(p == everywhere for p in placements),
           f"a batch has no shard on some of the {device['count']} devices: "
           f"{sorted(map(sorted, set(placements)))}")


def _per_device_peaks() -> dict:
    """Peak memory on every device, from the runtime: ``in_use`` is live
    buffers (state, batches), ``reserved`` what the running programs took
    for their temporaries (libtpu 0.0.34 books them apart).  Replicated
    state and the same program on each chip show as peaks of one order
    (the first device also ran the un-sharded init); a chip left out
    shows as next to nothing."""
    import jax

    stats = [d.memory_stats() for d in jax.devices()]
    out = {}
    for name in ("in_use", "reserved"):
        peaks = [s[f"peak_bytes_{name}"] for s in stats]
        _check(4 * min(peaks) >= max(peaks),
               f"per-device peak bytes {name} are not of one order: {peaks}")
        out[f"peak_{name}_gb_per_device"] = [round(p / 1e9, 2) for p in peaks]
    return out


# ---------------------------------------------------------------- the child

def run_leg(name: str) -> None:
    t0 = time.perf_counter()
    sys.path.insert(0, ROOT)
    from pytorch_distributed_tpu.utils.chip import require_tpu

    device = require_tpu()
    print(f"[chip_smoke:{name}] platform={device['platform']} "
          f"device_kind={device['kind']!r} count={device['count']}",
          flush=True)
    if device["platform"] != "tpu":
        sys.exit(f"[chip_smoke:{name}] platform is {device['platform']!r}, "
                 "not 'tpu'")

    import jax.monitoring

    from pytorch_distributed_tpu.obs.watchdog import BACKEND_COMPILE_EVENT
    from pytorch_distributed_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    compile_s = [0.0]

    def on_event(event: str, duration_secs: float, **_kw) -> None:
        if event == BACKEND_COMPILE_EVENT:  # a cache hit reports its load
            compile_s[0] += duration_secs

    jax.monitoring.register_event_duration_secs_listener(on_event)
    cache_dir = enable_compile_cache()
    observed = LEGS[name](device)
    result = {"leg": name, "ok": True, "device": device,
              "wall_s": round(time.perf_counter() - t0, 1),
              "compile_s": round(compile_s[0], 1),
              "compile_cache": cache_dir, **observed}
    with open(os.path.join(OUT, f"{name}.json"), "w") as f:
        json.dump(result, f, indent=1)
        f.write("\n")


# --------------------------------------------------------------- the parent

def _own_group_dying_with_parent() -> None:
    """Child side, between fork and exec: a process group of its own, and
    SIGKILL from the kernel should this parent die first (however)."""
    os.setsid()
    ctypes.CDLL(None).prctl(1, signal.SIGKILL)  # PR_SET_PDEATHSIG


def _run_child(name: str, timeout_s: float) -> int:
    """One leg, output to ``chip_smoke_out/NAME.log``.  Its whole process
    group is killed on timeout, interrupt or exit, so nothing started here
    outlives the smoke or keeps the chip."""
    with open(os.path.join(OUT, f"{name}.log"), "w") as log:
        child = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--leg", name],
            cwd=ROOT, stdout=log, stderr=subprocess.STDOUT,
            preexec_fn=_own_group_dying_with_parent)
        try:
            return child.wait(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            print(f"[chip_smoke] leg {name} exceeded {timeout_s:.0f}s",
                  flush=True)
            return 124
        finally:
            try:
                os.killpg(child.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            child.wait()


def _tail(path: str, n_bytes: int = 6000) -> str:
    with open(path, "rb") as f:
        f.seek(0, os.SEEK_END)
        f.seek(max(0, f.tell() - n_bytes))
        return f.read().decode(errors="replace")


def main() -> int:
    t0 = time.monotonic()
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))  # run finally:
    shutil.rmtree(OUT, ignore_errors=True)
    os.makedirs(OUT)
    results = []
    for name in LEGS:
        left = DEADLINE_S - (time.monotonic() - t0)
        rc = _run_child(name, left) if left > 0 else 124
        result_path = os.path.join(OUT, f"{name}.json")
        if rc != 0 or not os.path.exists(result_path):
            print(f"[chip_smoke] leg {name} FAILED (exit code {rc}); the end "
                  f"of chip_smoke_out/{name}.log:\n"
                  f"{_tail(os.path.join(OUT, name + '.log'))}", flush=True)
            return rc or 1
        with open(result_path) as f:
            results.append(json.load(f))
        r = results[-1]
        print(f"[chip_smoke] leg {name} ok: wall {r['wall_s']}s of which "
              f"compiling {r['compile_s']}s (set-up, not a metric); "
              + json.dumps({k: v for k, v in r.items() if k not in (
                  "leg", "ok", "device", "wall_s", "compile_s")}),
              flush=True)
    devices = [r["device"] for r in results]
    if any(d != devices[0] for d in devices):
        print(f"[chip_smoke] legs disagree on the device: {devices}")
        return 1
    with open(os.path.join(OUT, "summary.json"), "w") as f:
        json.dump({"ok": True, "device": devices[0],
                   "total_wall_s": round(time.monotonic() - t0, 1),
                   "legs": results}, f, indent=1)
        f.write("\n")
    print(json.dumps({"ok": True, "device": devices[0]}), flush=True)
    return 0


if __name__ == "__main__":
    if len(sys.argv) == 3 and sys.argv[1] == "--leg":
        run_leg(sys.argv[2])
    else:
        sys.exit(main())

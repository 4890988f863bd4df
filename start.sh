#!/usr/bin/env bash
# Canonical launch lines, one per recipe — reference start.sh:1-5 parity.
# For smoke runs on a non-TPU host, prefix any line with
#   JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8
# to simulate an 8-chip mesh on CPU (this is how the tests run).
# On a TPU host, `python chip_smoke.py` first: it proves the trainers and the
# serving engine start, compile and step on the chip.  One process per chip
# (one process drives all the chips of a host): never start a recipe from a
# parent that has already touched JAX.  Compiled programs are cached in
# $JAX_COMPILATION_CACHE_DIR if set, else in ./.jax_cache (git-ignored).

# 1. self-contained multi-process DP (ref start.sh:1: python multiprocessing_distributed.py)
python -m pytorch_distributed_tpu.recipes.multiprocessing_distributed --data "$DATA"

# 2. external-launcher DP (ref start.sh:2: torch.distributed.launch --nproc_per_node=4 distributed.py)
#    On GPU-style clusters the launcher exports PTD_TPU_*; on a TPU pod none needed.
PTD_TPU_COORDINATOR=127.0.0.1:23456 PTD_TPU_NUM_PROCESSES=1 PTD_TPU_PROCESS_ID=0 \
  python -m pytorch_distributed_tpu.recipes.distributed --data "$DATA"

# 3. bf16 mixed precision (ref start.sh:3: torch.distributed.launch apex_distributed.py)
python -m pytorch_distributed_tpu.recipes.apex_distributed --data "$DATA"

# 4. explicit collectives + compressed wire grads (ref start.sh:4: horovodrun -np 4 horovod_distributed.py)
python -m pytorch_distributed_tpu.recipes.horovod_distributed --data "$DATA"
# python -m pytorch_distributed_tpu.recipes.horovod_distributed --data "$DATA" --sync-bn   # cross-replica BN moments (torch SyncBatchNorm; round 5)

# 5. multi-node SLURM / multi-slice pod (ref start.sh:5: srun -N2 --gres gpu:4 distributed_slurm_main.py)
# srun -N2 --ntasks-per-node=1 python -m pytorch_distributed_tpu.recipes.distributed_slurm_main --data "$DATA"

# 6. single-process DataParallel baseline (ref README.md:86: python dataparallel.py)
python -m pytorch_distributed_tpu.recipes.dataparallel --data "$DATA"

# 7. canonical TPU-native recipe (BASELINE.json north star)
python -m pytorch_distributed_tpu.recipes.tpu_native --data "$DATA" -a resnet50

# 8. long-context LM pretraining (beyond reference): composable parallelism
python -m pytorch_distributed_tpu.recipes.lm_pretrain --tp 4 --seq-len 2048 -b 32 --steps 1000
# python -m pytorch_distributed_tpu.recipes.lm_pretrain --sp 4 --seq-len 16384 -b 8 --steps 1000
# python -m pytorch_distributed_tpu.recipes.lm_pretrain --tp 2 --sp 2 --seq-len 8192 -b 8 --steps 1000   # composed mesh
# python -m pytorch_distributed_tpu.recipes.lm_pretrain --pp 4 --n-layers 8 -b 32 --steps 1000           # GPipe pipeline
# python -m pytorch_distributed_tpu.recipes.lm_pretrain --pp 4 --schedule 1f1b --n-layers 8 -b 32 --microbatches 16 --steps 1000        # memory-bounded 1F1B
# python -m pytorch_distributed_tpu.recipes.lm_pretrain --pp 4 --schedule interleaved --pp-virtual 2 --n-layers 8 -b 32 --steps 1000    # virtual-stage 1F1B
# python -m pytorch_distributed_tpu.recipes.lm_pretrain --ep 4 --moe-top-k 2 -b 32 --steps 1000          # MoE top-2
# python -m pytorch_distributed_tpu.recipes.lm_pretrain --pp 2 --sp 2 --tp 2 -b 16 --steps 1000          # quad mesh
# python -m pytorch_distributed_tpu.recipes.lm_pretrain --fsdp --tp 2 -b 32 --steps 1000                 # ZeRO-3 + TP
# python -m pytorch_distributed_tpu.recipes.lm_pretrain --vocab 32000 --fused-ce 8 -b 16 --steps 1000     # fused tied-head+CE (big-vocab memory lever, round 5)

# 8b. LM serving (KV-cached decode; see also --tp N and --quant int8)
# python -m pytorch_distributed_tpu.recipes.lm_generate --resume runs/lm/checkpoint.msgpack --vocab 256 --prompt 'def main(' -n 64 --temperature 0.8 --top-p 0.9
# python -m pytorch_distributed_tpu.recipes.lm_generate --resume target.msgpack --spec-draft draft.msgpack --spec-gamma 4 --vocab 256 --prompt 'def main(' -n 64   # speculative decoding

# 9. full native input path on real data (C++ JPEG decode + u8 wire)
# python -m pytorch_distributed_tpu.recipes.tpu_native --data "$DATA" -a resnet50 --wire native

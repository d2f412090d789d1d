"""Production training launcher.

On a real fleet each host runs this under its TPU runtime (jax.distributed
initializes from the cluster env); on CPU it runs reduced configs end to end.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2.5-3b --reduced \
        --steps 100 --ckpt-dir /tmp/run1
    # multi-host (sketch): srun ... python -m repro.launch.train --arch ... \
    #     --mesh-data 16 --mesh-model 16 [--multi-pod] [--compress-pods]
"""
from __future__ import annotations

import argparse

import jax

from repro import optim
from repro.configs import get_config
from repro.data import DataConfig
from repro.distributed import shardlib
from repro.distributed.sharding import activation_rules
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.train import Trainer, TrainConfig, TrainerConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--mesh-data", type=int, default=0,
                    help=">0: build a (data, model) mesh and shard")
    ap.add_argument("--mesh-model", type=int, default=1)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--compress-pods", action="store_true",
                    help="error-feedback int8 allreduce on the pod axis")
    ap.add_argument("--distributed-init", action="store_true",
                    help="call jax.distributed.initialize() (real clusters)")
    args = ap.parse_args()

    if args.distributed_init:
        jax.distributed.initialize()

    enable_compile_cache()
    cfg = get_config(args.arch, reduced=args.reduced)
    if args.mesh_data:
        if args.multi_pod:
            mesh = make_mesh((2, args.mesh_data, args.mesh_model),
                             ("pod", "data", "model"))
        else:
            mesh = make_mesh((args.mesh_data, args.mesh_model),
                             ("data", "model"))
        shardlib.set_mesh(mesh)
        shardlib.set_rules(activation_rules(mesh))

    tcfg = TrainConfig(
        optimizer=optim.AdamWConfig(lr=args.lr, warmup_steps=args.steps // 10,
                                    total_steps=args.steps),
        microbatches=args.microbatches,
        compress_pod_axis="pod" if args.compress_pods else None,
    )
    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq_len,
                      global_batch=args.global_batch,
                      num_hosts=jax.process_count(),
                      host_id=jax.process_index())
    run = TrainerConfig(total_steps=args.steps,
                        checkpoint_every=args.ckpt_every,
                        checkpoint_dir=args.ckpt_dir, log_every=10)

    def log(step, metrics):
        print(f"step {step}: " + " ".join(
            f"{k}={float(v):.4f}" if hasattr(v, "__float__") else f"{k}={v}"
            for k, v in metrics.items()), flush=True)

    result = Trainer(cfg, tcfg, run, dcfg, log_fn=log).train()
    print(f"finished at step {result['final_step']}; "
          f"{len(result['stragglers'])} straggler steps")


if __name__ == "__main__":
    main()

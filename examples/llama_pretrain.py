"""Elastic Llama pretraining — the flagship example.

Run single-host (CPU demo, 8 virtual devices):

    LOCAL_DEVICES=8 \
    dlrover-tpu-run --standalone --nnodes=1 --nproc_per_node=1 \
        --accelerator=cpu examples/llama_pretrain.py -- \
        --model tiny --steps 20 --fsdp 2 --tp 2

One TPU chip, Llama-3-8B widths cut to the depth 16 GB holds (what
``chip_smoke.py`` runs; ``--accelerator=tpu`` is the default and fails
when JAX finds no TPU):

    dlrover-tpu-run --standalone --nnodes=1 --nproc_per_node=1 \
        examples/llama_pretrain.py -- --model 8b --layers 2 \
        --param-dtype bfloat16 --seq 2048 --micro-batch 1 --global-batch 1

Multi-host TPU (per host, master already up):

    dlrover-tpu-run --master_addr $MASTER:50051 --nnodes=2:8 \
        --network-check --ckpt-replica examples/llama_pretrain.py -- \
        --model 8b --fsdp 8 --tp 4 --ckpt-dir /mnt/ckpt

The script is fully elastic: a membership change re-runs rendezvous,
the trainer re-derives gradient accumulation so the global batch is
unchanged, and state restores from shm/replica/storage.
"""

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import dlrover_tpu.train as dtrain


def parse_args():
    p = argparse.ArgumentParser("llama_pretrain")
    p.add_argument("--model", default="tiny",
                   choices=["tiny", "1b", "8b"])
    p.add_argument("--layers", type=int, default=0,
                   help="cut the model to this depth (0 = its own)")
    p.add_argument("--param-dtype", default="",
                   choices=["", "float32", "bfloat16"],
                   help="params and adam moments dtype (empty = the "
                        "model's own)")
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--global-batch", type=int, default=0,
                   help="0 = pick per model")
    p.add_argument("--micro-batch", type=int, default=2)
    p.add_argument("--seq", type=int, default=0, help="0 = model default")
    p.add_argument("--devices", type=int, default=0,
                   help="build the mesh from the first N devices only "
                        "(0 = all the process sees)")
    p.add_argument("--fsdp", type=int, default=1)
    p.add_argument("--tp", type=int, default=1)
    p.add_argument("--sp", type=int, default=1)
    p.add_argument("--zero1", action="store_true",
                   help="ZeRO-1 weight-update sharding across dp "
                   "(train/zero1.py)")
    p.add_argument("--ckpt-dir", default="/tmp/llama_pretrain_ckpt")
    p.add_argument("--save-every", type=int, default=10)
    p.add_argument("--data", default="",
                   help="flat binary token file (nanoGPT/Megatron .bin "
                        "convention; see dlrover_tpu.train.datasets); "
                        "empty = synthetic tokens")
    p.add_argument("--data-dtype", default="uint16",
                   choices=["uint16", "uint32", "int32"])
    return p.parse_args()


def model_config(name, llama, jnp):
    if name == "tiny":
        return llama.LlamaConfig.tiny(), 16
    if name == "1b":
        return llama.LlamaConfig(
            vocab_size=32768, dim=2048, n_layers=16, n_heads=16,
            n_kv_heads=16, ffn_dim=8192, max_seq_len=2048,
            rope_theta=10000.0, dtype=jnp.bfloat16,
            param_dtype=jnp.bfloat16,
        ), 64
    return llama.LlamaConfig(), 1024  # 8B-class defaults


def main():
    args = parse_args()
    # LOCAL_DEVICES forces N virtual devices on the CPU demo path
    n = os.environ.get("LOCAL_DEVICES")
    ctx = dtrain.init(local_device_count=int(n) if n else None)

    import jax
    import jax.numpy as jnp
    import optax

    from dlrover_tpu.checkpoint.checkpointer import Checkpointer
    from dlrover_tpu.models import llama
    from dlrover_tpu.parallel import MeshConfig, build_mesh, named_shardings
    from dlrover_tpu.train.trainer import ElasticTrainer, TrainConfig

    cfg, default_gb = model_config(args.model, llama, jnp)
    full_depth = cfg.n_layers
    if args.layers:
        cfg = dataclasses.replace(cfg, n_layers=args.layers)
    if args.param_dtype:
        cfg = dataclasses.replace(
            cfg, param_dtype=jnp.dtype(args.param_dtype)
        )
    seq = args.seq or cfg.max_seq_len
    dev = jax.devices()[0]
    print(f"device platform={dev.platform} kind={dev.device_kind!r} "
          f"count={len(jax.devices())}", flush=True)
    devices = jax.devices()[: args.devices or None]
    mc = MeshConfig(dp=-1, fsdp=args.fsdp, sp=args.sp, tp=args.tp).resolve(
        len(devices)
    )
    mesh = build_mesh(mc, devices=devices)
    specs = llama.param_specs(cfg)
    init_params = jax.jit(
        lambda k: llama.init_params(cfg, k),
        out_shardings=named_shardings(mesh, specs),
    )

    tc = TrainConfig(
        global_batch_size=args.global_batch or default_gb,
        micro_batch_size=args.micro_batch,
        total_steps=args.steps,
        zero1=args.zero1,
    )
    trainer = ElasticTrainer(
        lambda p, t: llama.loss_fn(p, t, cfg, mesh),
        specs, mesh, mc, tc, worker_ctx=ctx,
    )
    # semantic hints for the shardcheck IR rules (DLROVER_TPU_SHARDCHECK):
    # SC003 needs seq/vocab to recognize a dense-logits materialization
    trainer.shardcheck_hints = {
        "seq_len": seq, "vocab": cfg.vocab_size,
    }

    def fresh_state():
        return trainer.init_state(init_params(jax.random.key(0)))

    def release(tree):
        for leaf in jax.tree.leaves(tree):
            leaf.delete()

    state = fresh_state()
    n_params = llama.param_count(cfg)
    state_bytes = sum(l.nbytes for l in jax.tree.leaves(state))
    print(f"config model={args.model} dim={cfg.dim} "
          f"layers={cfg.n_layers}/{full_depth} heads={cfg.n_heads}/"
          f"{cfg.n_kv_heads} ffn={cfg.ffn_dim} vocab={cfg.vocab_size} "
          f"seq={seq} dtype={jnp.dtype(cfg.dtype).name} "
          f"param_dtype={jnp.dtype(cfg.param_dtype).name} "
          f"remat={cfg.remat_policy if cfg.remat else 'off'} "
          f"mesh={dict(mesh.shape)} batch={tc.global_batch_size} "
          f"params={n_params} state_bytes={state_bytes}", flush=True)
    # where the parameters live: bytes of parameter shards per device
    per_dev = {}
    for leaf in jax.tree.leaves(state["params"]):
        for sh in leaf.addressable_shards:
            per_dev[sh.device.id] = per_dev.get(sh.device.id, 0) \
                + sh.data.nbytes
    print(f"param_bytes_per_device {dict(sorted(per_dev.items()))}",
          flush=True)

    ckpt = Checkpointer(args.ckpt_dir, save_storage_interval=args.save_every)
    # Restore against the state's shapes and shardings, not its buffers:
    # a job that fills the device cannot hold the initial and the
    # restored state at once. The initial buffers go first, and a miss
    # builds them again.
    target = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype,
                                       sharding=l.sharding),
        state,
    )
    release(state)
    restored = ckpt.load(target=target)
    start = 0
    if restored is None:
        state = fresh_state()
    else:
        start, state = restored
        # seed the host step counter so report_step never regresses
        # the master's SpeedMonitor after a restart
        trainer.sync_host_step(state)
        tier = ckpt.last_restore_stats.get("tier", "")
        print(f"restored from step {start} tier={tier}", flush=True)

    a, b = trainer.step_batch_shape
    loader_iter = None
    loader = None
    # per-host filename: shared ckpt dirs must not have N hosts racing
    # one file (every host's content is identical, but torn concurrent
    # writes are not)
    loader_state_path = os.path.join(
        args.ckpt_dir, f"loader_state-{jax.process_index()}.json"
    )
    if args.data:
        import numpy as np

        from dlrover_tpu.train.data import (
            ElasticDataLoader,
            ElasticDistributedSampler,
        )
        from dlrover_tpu.train.datasets import TokenFileDataset

        dataset = TokenFileDataset(args.data, seq_len=seq,
                                   dtype=args.data_dtype)
        dataset.validate_vocab(cfg.vocab_size)
        if len(dataset) < a * b:
            raise SystemExit(
                f"--data has only {len(dataset)} sequences of seq={seq}; "
                f"need at least one global batch of {a * b}"
            )
        # every host draws the IDENTICAL global batch (num_replicas=1):
        # the trainer's jitted step expects the same (a, b, seq) array on
        # all processes and slices each device's shard from it. For
        # corpora too large to read fully from every host, switch to the
        # master-driven ShardingClient flow (docs/tutorial).
        sampler = ElasticDistributedSampler(
            dataset_size=len(dataset), batch_size=a * b,
            num_replicas=1, rank=0, shuffle=True, seed=1,
        )
        loader = ElasticDataLoader(
            dataset, batch_size=a * b, sampler=sampler,
            collate=lambda xs: np.stack(xs).reshape(a, b, seq),
        )
        if restored is not None:
            side = None
            if os.path.exists(loader_state_path):
                try:
                    with open(loader_state_path) as f:
                        side = json.load(f)
                except ValueError:
                    side = None  # torn write: fall back to epoch start
            # discard a sidecar AHEAD of the restored model (the disk
            # persist is async; a crash inside that window must replay
            # data, never skip it)
            if side is not None and side.get("step", 0) > start:
                side = None
            # cross-host agreement: hosts whose renames straddled the
            # kill hold different steps; every host must load the SAME
            # position or none (the jitted step requires the identical
            # global batch on all processes)
            my_step = side["step"] if side is not None else -1
            if jax.process_count() > 1:
                from jax.experimental import multihost_utils
                import numpy as np

                steps = np.asarray(multihost_utils.process_allgather(
                    np.array([my_step])
                )).reshape(-1)
                if not (steps == steps[0]).all() or steps[0] < 0:
                    side = None
            if side is not None:
                loader.load_state_dict(side["loader"])
                print("loader position restored", flush=True)

        from collections import deque

        # sampler positions AFTER each produced batch: prefetch pulls
        # ahead, so the sidecar must record the CONSUMED position, not
        # the sampler's (which runs up to `size` batches ahead)
        state_q: deque = deque()

        def batches():
            while True:  # loop epochs; the step budget bounds the run
                for b_ in loader:
                    state_q.append(loader.state_dict())
                    yield b_

        # keep 2 batches in flight on-device: h2d rides behind compute,
        # placed straight onto the step's batch sharding. Every host
        # holds the IDENTICAL global batch (num_replicas=1), so
        # multi-host uses prefetch's replicated mode (each device slices
        # its shard from the global value).
        from dlrover_tpu.train.data import prefetch_to_device

        loader_iter = prefetch_to_device(
            batches(), sharding=trainer.batch_sharding, replicated=True
        )

    loader_pos = None
    for step in range(start, args.steps):
        if loader_iter is not None:
            batch = next(loader_iter)
            loader_pos = state_q.popleft()  # position of THIS batch
        else:
            # synthetic tokens; --data switches to the memmapped corpus
            batch = jax.random.randint(
                jax.random.fold_in(jax.random.key(1), step), (a, b, seq),
                0, cfg.vocab_size,
            )
        t0 = time.perf_counter()
        state, loss = trainer.step(state, batch)
        loss = float(loss)  # waits for the device: step_s is the step
        step_s = time.perf_counter() - t0
        save_s = ckpt.save(step + 1, state)
        if loader is not None and (step + 1) % args.save_every == 0:
            # data position rides a per-host sidecar stamped with the
            # step: restore discards it when it is AHEAD of the restored
            # model (the storage persist is async), so a crash replays
            # data rather than skipping it. tmp+rename keeps each write
            # atomic against SIGKILL.
            os.makedirs(args.ckpt_dir, exist_ok=True)
            tmp = loader_state_path + ".tmp"
            with open(tmp, "w") as f:
                json.dump({"step": step + 1, "loader": loader_pos}, f)
            os.replace(tmp, loader_state_path)
        if jax.process_index() == 0:
            print(f"step {step + 1} loss {loss:.4f} step_s {step_s:.3f} "
                  f"save_s {save_s:.3f}", flush=True)
    # what the optimizer has seen of the gradients: the norm of adam's
    # first moment per parameter group. Two layouts of one run agree on
    # these only if their backward passes do; the loss of a few warm-up
    # steps hardly moves with the update.
    norms = jax.jit(lambda mu: {
        k: optax.global_norm(jax.tree.map(lambda m: m.astype(jnp.float32), v))
        for k, v in mu.items()
    })(optax.tree_utils.tree_get(state["opt"], "mu"))
    if jax.process_index() == 0:
        print("first_moment_norms " + json.dumps(
            {k: float(v) for k, v in norms.items()}), flush=True)
    # close() waits until the last persist has been copied out of shm;
    # do not sit on the state's device buffers, and on the host copies
    # the last save cached on them, meanwhile
    ckpt.wait_staging()
    release(state)
    ckpt.close()
    stats = dev.memory_stats() or {}
    print(f"peak_bytes_in_use {stats.get('peak_bytes_in_use')}", flush=True)
    print("DONE", flush=True)


if __name__ == "__main__":
    main()

"""Elastic sparse-MoE pretraining with expert parallelism.

    LOCAL_DEVICES=8 STEPS=10 \
    dlrover-tpu-run --standalone --nnodes=1 --nproc_per_node=1 \
        --accelerator=cpu examples/moe_pretrain.py --model olmoe

``--model mixtral|olmoe|xing4|kimi_linear`` picks the family's
conventions (Mixtral: top-2 of 8, renormalised; OLMoE: top-8 of 64, not renormalised,
QK-norm; Xing4.0: latent attention, four residual streams, sigmoid top-4
of 64 with a shared expert, a leading dense layer and a multi-token head,
models/xing4.py; Kimi-Linear: three gated-delta-rule layers to one
latent-attention layer without rotary in one layer pattern, sigmoid top-8
of 256 with a shared expert, models/kimi_linear.py) at a toy size;
``--full`` takes the published widths of the preset instead. Experts shard over the ``ep`` mesh axis; routing is
dropless (sorted dispatch into a grouped matmul, models/moe.py) and the
rows travel over ep inside the jitted step.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import dlrover_tpu.train as dtrain

_n = os.environ.get("LOCAL_DEVICES")
ctx = dtrain.init(local_device_count=int(_n) if _n else None)

import jax

from dlrover_tpu.checkpoint.checkpointer import Checkpointer
from dlrover_tpu.models import kimi_linear, moe, xing4
from dlrover_tpu.parallel import MeshConfig, build_mesh, named_shardings
from dlrover_tpu.train.trainer import ElasticTrainer, TrainConfig

STEPS = int(os.environ.get("STEPS", "10"))
SEQ = int(os.environ.get("SEQ", "64"))

n_dev = len(jax.devices())
ep = 2 if n_dev % 2 == 0 else 1
mc = MeshConfig(dp=-1, fsdp=1, ep=ep, sp=1, tp=1).resolve(n_dev)
mesh = build_mesh(mc)

ap = argparse.ArgumentParser()
ap.add_argument("--model",
                choices=("mixtral", "olmoe", "xing4", "kimi_linear"),
                default="mixtral")
ap.add_argument("--full", action="store_true",
                help="the preset's published widths, not the toy size")
args = ap.parse_args()

if args.model == "xing4":
    family = xing4
    cfg = (xing4.Xing4Config() if args.full
           else xing4.Xing4Config.tiny(max_seq_len=SEQ))
elif args.model == "kimi_linear":
    family = kimi_linear
    cfg = (kimi_linear.KimiLinearConfig() if args.full
           else kimi_linear.KimiLinearConfig.tiny())
else:
    family = moe
    preset = {"mixtral": moe.MoeConfig.mixtral_8x7b,
              "olmoe": moe.MoeConfig.olmoe_1b_7b}[args.model]()
    if args.full:
        cfg = preset
    else:
        # the family's conventions and its experts-to-choices ratio, toy
        # widths
        cfg = moe.MoeConfig.tiny(
            n_heads=4, n_kv_heads=2, max_seq_len=SEQ,
            n_experts=min(preset.n_experts, 16),
            experts_per_token=min(preset.experts_per_token, 4),
            norm_topk_prob=preset.norm_topk_prob, qk_norm=preset.qk_norm,
        )
specs = family.param_specs(cfg)
params = jax.jit(
    lambda k: family.init_params(cfg, k),
    out_shardings=named_shardings(mesh, specs),
)(jax.random.key(0))

tc = TrainConfig(
    global_batch_size=2 * mc.data_parallel_size, micro_batch_size=2,
    total_steps=STEPS,
)
trainer = ElasticTrainer(
    lambda p, t: family.loss_fn(p, t, cfg, mesh), specs, mesh, mc, tc,
    worker_ctx=ctx,
)
state = trainer.init_state(params)

ckpt = Checkpointer("/tmp/moe_pretrain_ckpt", save_storage_interval=5)
restored = ckpt.load(target=state)
start = 0
if restored is not None:
    start, state = restored

a, b = trainer.step_batch_shape
for step in range(start, STEPS):
    batch = jax.random.randint(
        jax.random.fold_in(jax.random.key(1), step), (a, b, SEQ), 0,
        cfg.vocab_size,
    )
    state, loss = trainer.step(state, batch)
    ckpt.save(step + 1, state)
    if jax.process_index() == 0:
        print(f"step {step + 1} loss {float(loss):.4f}", flush=True)
ckpt.close()
print("DONE", flush=True)

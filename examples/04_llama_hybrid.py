"""BASELINE config 4 — LLaMA hybrid tensor x data parallel (+ sequence
parallel + recompute).

Full shape of the reference recipe: VocabParallel embedding and
Column/Row-parallel attention/MLP over the mp axis, Megatron sequence
parallelism, activation recompute, hybrid-parallel optimizer with
TP-aware global-norm clip.  At scale: llama_config("7b"),
tp=8 x dp=4, rotary position embeddings and the fused Pallas kernels
engage on TPU automatically.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))   # run from a source checkout

import numpy as np

import paddle_tpu as paddle
import paddle_tpu.optimizer as opt
from paddle_tpu.distributed import fleet
from paddle_tpu.jit import train_step
from paddle_tpu.models import LlamaForCausalLM, llama_config


def main():
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": 4,
                               "pp_degree": 1, "sharding_degree": 1}
    fleet.init(is_collective=True, strategy=strategy)

    paddle.seed(0)
    cfg = llama_config("tiny", sequence_parallel=True,
                       use_recompute=True)
    model = fleet.distributed_model(LlamaForCausalLM(cfg))
    inner = getattr(model, "_layers", model)
    optimizer = opt.AdamW(
        learning_rate=3e-4, parameters=inner.parameters(),
        weight_decay=0.1,
        grad_clip=paddle.nn.ClipGradByGlobalNorm(1.0))
    optimizer = fleet.distributed_optimizer(optimizer)

    step = train_step(inner, inner.loss_fn, optimizer)
    rs = np.random.RandomState(0)
    B, S = 4, 32
    for i in range(3):
        ids = rs.randint(0, cfg.vocab_size, (B, S)).astype("int64")
        loss = step(ids, ids)
        print(f"step {i}: loss {float(loss):.4f}")
    print("hybrid tp x dp training OK (sp + recompute on)")


if __name__ == "__main__":
    main()

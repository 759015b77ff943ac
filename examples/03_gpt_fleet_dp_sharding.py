"""BASELINE config 3 — GPT pretraining, fleet dp + ZeRO sharding.

The north-star configuration's full shape: fleet topology, sharding
stage 2 (optimizer-state + gradient sharding over the mesh), AMP O2
with fp32 master weights and dynamic loss scaling, global-norm clip,
distributed checkpoint save/load.  At scale: gpt_config("gpt3-1.3B"),
dp=4 x sharding=8 on a v5p-32 slice.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))   # run from a source checkout

import numpy as np

import paddle_tpu as paddle
import paddle_tpu.optimizer as opt
from paddle_tpu import amp
from paddle_tpu.distributed import fleet
from paddle_tpu.jit import train_step
from paddle_tpu.models import GPTForPretraining, gpt_config


def main():
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 4, "sharding_degree": 2,
                               "mp_degree": 1, "pp_degree": 1}
    strategy.sharding_configs = {"stage": 2}
    fleet.init(is_collective=True, strategy=strategy)

    paddle.seed(0)
    cfg = gpt_config("tiny", hidden_dropout_prob=0.0,
                     attention_dropout_prob=0.0)
    model = fleet.distributed_model(GPTForPretraining(cfg))
    inner = getattr(model, "_layers", model)
    optimizer = opt.AdamW(
        learning_rate=1e-4, parameters=inner.parameters(),
        weight_decay=0.01, multi_precision=True,
        grad_clip=paddle.nn.ClipGradByGlobalNorm(1.0))
    optimizer = fleet.distributed_optimizer(optimizer)
    inner_m, optimizer = amp.decorate(models=inner, optimizers=optimizer,
                                      level="O2", dtype="bfloat16")
    scaler = amp.GradScaler(init_loss_scaling=2.0 ** 10)

    step = train_step(inner_m, inner_m.loss_fn, optimizer, scaler=scaler)
    rs = np.random.RandomState(0)
    B, S = 8, 32
    for i in range(3):
        ids = rs.randint(0, cfg.vocab_size, (B, S)).astype("int64")
        loss = step(ids, ids)
        print(f"step {i}: loss {float(loss):.4f} "
              f"scale {float(scaler._scale):.0f}")

    # distributed checkpoint round-trip (resharding-capable)
    from paddle_tpu.distributed import checkpoint as dck
    state = {"model": inner_m.state_dict(), "opt": optimizer.state_dict()}
    dck.save_state_dict(state, "/tmp/gpt_example_ckpt")
    dck.load_state_dict(state, "/tmp/gpt_example_ckpt")
    print("distributed checkpoint round-trip OK")


if __name__ == "__main__":
    main()

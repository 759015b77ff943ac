"""BASELINE config 5 — ERNIE-MoE with expert parallelism + semi-auto
Engine.

Full shape of the reference recipe: MoE blocks with GShard top-2
gating, stacked experts sharded over the real ``ep`` mesh axis
(vectorized expert compute; capacity-based dispatch), auto_parallel
Engine.fit with the XLA-backed cost model.  At scale:
ernie_moe_config("base"), ep=8 x dp=4, global_scatter/gather become
all-to-all over ICI.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))   # run from a source checkout

import numpy as np

import paddle_tpu as paddle
import paddle_tpu.optimizer as opt
from paddle_tpu.distributed import fleet
from paddle_tpu.distributed.auto_parallel import Engine
from paddle_tpu.models import ErnieMoEForPretraining, ernie_moe_config


class MLMData:
    def __init__(self, cfg, n=8):
        self.cfg, self.n = cfg, n

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        rs = np.random.RandomState(i)
        ids = rs.randint(0, self.cfg.vocab_size, (4, 16)).astype("int64")
        labels = ids.copy()
        labels[rs.rand(4, 16) > 0.3] = -100
        return ids, labels


def main():
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 2, "ep_degree": 4,
                               "mp_degree": 1, "pp_degree": 1,
                               "sharding_degree": 1}
    fleet.init(is_collective=True, strategy=strategy)
    hcg = fleet.get_hybrid_communicate_group()
    assert hcg.get_expert_parallel_world_size() == 4

    paddle.seed(0)
    cfg = ernie_moe_config("tiny", hidden_dropout_prob=0.0,
                           attention_dropout_prob=0.0)
    model = ErnieMoEForPretraining(cfg)
    optimizer = opt.AdamW(learning_rate=1e-3,
                          parameters=model.parameters())
    engine = Engine(model, loss=model.loss_fn, optimizer=optimizer)
    history = engine.fit(MLMData(cfg), batch_size=None, epochs=1)
    print("losses:", [round(l, 4) for l in history["loss"]])
    print("Engine.cost (est. step ms, bytes):", engine.cost())


if __name__ == "__main__":
    main()

"""BASELINE config 2 — BERT masked-LM pretraining under @to_static.

Full shape of the reference recipe (dy2static trace + AdamW + save/load
inference parity) at toy scale; on hardware use bert_config("base"),
seq 384/512, the SQuAD head, and real WordPiece inputs via
paddle.text.FasterTokenizer.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))   # run from a source checkout

import numpy as np

import paddle_tpu as paddle
import paddle_tpu.optimizer as opt
from paddle_tpu.jit import InputSpec
from paddle_tpu.models import BertForPretraining, bert_config


def main():
    paddle.seed(0)
    cfg = bert_config("tiny", hidden_dropout_prob=0.0,
                      attention_dropout_prob=0.0)
    model = BertForPretraining(cfg)
    optimizer = opt.AdamW(learning_rate=1e-4,
                          parameters=model.parameters(),
                          weight_decay=0.01)

    # the compiled region is the model forward (the test strategy the
    # reference uses too); the loss stays eager on its outputs
    fwd = paddle.jit.to_static(model.forward)

    rs = np.random.RandomState(0)
    B, S = 4, 32
    ids = rs.randint(0, cfg.vocab_size, (B, S)).astype("int64")
    mask = np.ones((B, S), "int64")
    labels = ids.copy()
    labels[rs.rand(B, S) > 0.15] = -100       # MLM-style sparse labels

    for step in range(4):
        mlm_scores, nsp_scores = fwd(paddle.to_tensor(ids),
                                     attention_mask=paddle.to_tensor(mask))
        loss = model.loss_fn(mlm_scores, nsp_scores,
                             paddle.to_tensor(labels))
        loss.backward()
        optimizer.step()
        optimizer.clear_grad()
        print(f"step {step}: mlm loss {float(loss):.4f}")

    # export → reload → inference parity (the deployment path)
    model.eval()
    paddle.jit.save(model, "/tmp/bert_example",
                    input_spec=[InputSpec([None, S], "int64", "ids")])
    loaded = paddle.jit.load("/tmp/bert_example")
    got = loaded(paddle.to_tensor(ids))[0].numpy()
    want = model(paddle.to_tensor(ids))[0].numpy()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    print("jit.save/load inference parity OK")


if __name__ == "__main__":
    main()

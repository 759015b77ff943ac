"""Deployment surface — generation, PTQ, ONNX export, HTTP serving.

The post-training path a reference user walks after pretraining: decode
with the KV cache, quantize for inference, export the artifact, stand up
an endpoint.  Runs on the 8-device CPU mesh at toy scale; every step is
the same API that runs on a TPU chip.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))   # run from a source checkout

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
import jax  # noqa: E402

# default to the virtual CPU mesh; opt in to an attached chip with
# PADDLE_EXAMPLE_TPU=1
if os.environ.get("PADDLE_EXAMPLE_TPU") != "1":
    jax.config.update("jax_platforms", "cpu")

import tempfile  # noqa: E402

import numpy as np  # noqa: E402

import paddle_tpu as paddle  # noqa: E402
import paddle_tpu.nn as nn  # noqa: E402
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM  # noqa: E402


def main():
    paddle.seed(0)
    workdir = tempfile.mkdtemp(prefix="paddle_tpu_deploy_")

    # 1. a (toy) pretrained decoder + KV-cache generation ---------------
    model = LlamaForCausalLM(LlamaConfig(
        vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
        num_kv_heads=2, intermediate_size=128,
        max_position_embeddings=128))
    prompt = paddle.to_tensor(np.array([[5, 17, 42, 7]], np.int64))
    # NOTE each decode step compiles once per cache length on a fresh
    # process (XLA shape specialization); keep the toy run short
    paddle.seed(7)
    sampled = model.generate(prompt, max_new_tokens=6,
                             decode_strategy="sampling", top_k=20,
                             top_p=0.9, temperature=0.8)
    print("sampled:", sampled.numpy()[0].tolist())
    # serving-style decode: same tokens through the paged KV cache
    # (fixed-size page pool, the block-cache design production decode
    # uses — see ops/paged_attention.py)
    paddle.seed(7)
    paged = model.generate(prompt, max_new_tokens=6,
                           decode_strategy="sampling", top_k=20,
                           top_p=0.9, temperature=0.8,
                           use_paged_cache=True)
    assert paged.numpy()[0].tolist() == sampled.numpy()[0].tolist()
    print("paged decode reproduces the dense cache token-for-token")

    # 2. PTQ an MLP classifier head -------------------------------------
    from paddle_tpu.quantization import (PTQ, QuantConfig,
                                         FakeQuanterWithAbsMaxObserver)
    head = nn.Sequential(nn.Linear(64, 32), nn.ReLU(), nn.Linear(32, 8))
    head.eval()
    x = paddle.to_tensor(np.random.RandomState(0)
                         .randn(16, 64).astype(np.float32))
    fp32_out = head(x).numpy()
    ptq = PTQ(QuantConfig(activation=FakeQuanterWithAbsMaxObserver(),
                          weight=FakeQuanterWithAbsMaxObserver()))
    observed = ptq.quantize(head)
    for s in range(4):                      # calibration passes
        observed(paddle.to_tensor(np.random.RandomState(s)
                                  .randn(16, 64).astype(np.float32)))
    int8 = ptq.convert(observed)
    rel = np.abs(int8(x).numpy() - fp32_out).max() / np.abs(fp32_out).max()
    print(f"PTQ int8 deviation vs fp32: {rel:.4f}")

    # 3. ONNX export of the quantizable head's fp32 twin ----------------
    from paddle_tpu.jit.to_static import InputSpec
    onnx_path = paddle.onnx.export(
        head, os.path.join(workdir, "head"),
        input_spec=[InputSpec([None, 64], "float32")])
    print("ONNX artifact:", onnx_path,
          f"({os.path.getsize(onnx_path)} bytes)")

    # 4. StableHLO artifact + HTTP serving ------------------------------
    from paddle_tpu.jit import save as jit_save
    from paddle_tpu.inference.serving import serve, predict_http
    prefix = os.path.join(workdir, "served")
    jit_save(head, prefix, input_spec=[InputSpec([None, 64], "float32")])
    srv = serve(prefix)
    try:
        srv.warmup([x.numpy()])
        out = predict_http(srv.url, x.numpy())[0]
        np.testing.assert_allclose(out, fp32_out, rtol=1e-5, atol=1e-5)
        print("served at", srv.url, "— HTTP predict matches eager")
    finally:
        srv.stop()


if __name__ == "__main__":
    main()

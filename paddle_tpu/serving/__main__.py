"""``python -m paddle_tpu.serving`` — minimal continuous-batching demo.

Builds a tiny randomly-initialized GPT, starts the engine, submits a
handful of concurrent requests (two sharing a prompt prefix so the
prefix cache shows up in the stats) and prints the streamed tokens plus
the engine/scheduler counters.  Runs on the CPU backend in seconds; on
a TPU the same code routes through the Pallas ragged kernel.

Options::

    python -m paddle_tpu.serving [--requests N] [--max-new M]
                                 [--max-batch B] [--serve]

``--serve`` additionally exposes the engine over HTTP
(``InferenceServer`` + ``FLAGS_serving_engine``) and drives it through
``POST /generate`` instead of the in-process API.
"""
from __future__ import annotations

import argparse
import threading


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--serve", action="store_true",
                    help="drive the engine over HTTP (/generate)")
    args = ap.parse_args(argv)

    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining
    from paddle_tpu.serving import ServingEngine

    paddle.seed(0)
    cfg = GPTConfig(num_layers=2, hidden_size=64, num_heads=4,
                    vocab_size=256, max_position_embeddings=128,
                    hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
    model = GPTForPretraining(cfg)
    rs = np.random.RandomState(0)
    shared_prefix = rs.randint(0, 256, (16,)).tolist()
    prompts = [shared_prefix + rs.randint(0, 256, (4,)).tolist()
               for _ in range(2)]
    prompts += [rs.randint(0, 256, (rs.randint(4, 24),)).tolist()
                for _ in range(max(0, args.requests - 2))]

    engine = ServingEngine(model, max_batch=args.max_batch,
                           page_size=16)
    failed = []
    with engine:
        if args.serve:
            from paddle_tpu.flags import set_flags
            from paddle_tpu.inference.serving import (InferenceServer,
                                                      generate_http)
            set_flags({"FLAGS_serving_engine": True})
            srv = InferenceServer(engine=engine).start()
            print(f"serving on {srv.url}  (POST /generate)")

            def run(i, ids):
                try:
                    toks = list(generate_http(
                        srv.url, ids, max_new_tokens=args.max_new))
                except (RuntimeError, OSError) as e:
                    failed.append(i)
                    print(f"request {i}: prompt[{len(ids)}] -> "
                          f"FAILED: {e}")
                    return
                print(f"request {i}: prompt[{len(ids)}] -> {toks}")

            threads = [threading.Thread(target=run, args=(i, p))
                       for i, p in enumerate(prompts)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            srv.stop()
        else:
            reqs = [engine.submit(p, max_new_tokens=args.max_new)
                    for p in prompts]
            for i, req in enumerate(reqs):
                try:
                    toks = req.wait(timeout=120)
                except (RuntimeError, TimeoutError) as e:
                    # a quarantined / deadline-cancelled request fails
                    # alone — the remaining streams still complete
                    failed.append(i)
                    print(f"request {req.id}: prompt[{len(prompts[i])}] "
                          f"-> FAILED ({req.error_kind}): {e}")
                    continue
                print(f"request {req.id}: prompt[{len(prompts[i])}] "
                      f"-> {toks}")
        print("engine stats:", engine.stats())
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main())

"""The limit on the logits of a served GLM-5 model
(``benchmark/reference/glm5.py`` ``LOGITS_TOL``), and the table that
proves it at a small size on the CPU: the honest program — absorbed,
over its latent pools, its selection by a radix select and a top-k —
over twelve seeds far below it; the expanded reference computed in
bfloat16 and the reference with each mechanism of ``OMISSIONS`` left out
or got wrong above it on every seed.  (The same table at the published
widths, on the chip, is in PERF.md section 6, PR 33.)"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.glm5 import Glm5ForCausalLM

from benchmark.reference import glm5 as ref
from test_glm5_serving import (VOCAB, Step, _config, _reseed, reference,
                               worst)

_SEEDS = range(12)


@pytest.fixture(scope="module")
def table():
    """Each reading of the table over ``_SEEDS``: the worst of the nine
    checked rows (the prompt's last and eight decode steps) of a
    72-token sequence — more than four times ``index_topk`` 16 — as a
    share of the row's largest reference logit.  One model, one traced
    step and one compiled reference a variant serve every seed."""
    paddle.seed(0)
    m = Glm5ForCausalLM(_config())
    m.eval()
    jitted = jax.jit(m.build_ragged_decode_step()[1])
    rows = {name: [] for name in ("program", "bfloat16") + ref.OMISSIONS}
    for seed in _SEEDS:
        _reseed(m, 100 + seed)
        seq = np.random.RandomState(seed).randint(0, VOCAB, (72,))
        want = reference(m, seq)
        got = Step(m, [seq], jitted=jitted, width=32).run([64], 24)[0]
        rows["program"].append(worst(got, want))
        checked = dict.fromkeys(got)
        variants = [("bfloat16", dict(dtype=jnp.bfloat16))] \
            + [(o, dict(omit=(o,))) for o in ref.OMISSIONS]
        for name, change in variants:
            other = reference(m, seq, **change)
            rows[name].append(worst({p: other[p] for p in checked}, want))
    print("\nlogits error over", len(_SEEDS), "seeds (min / median / max):")
    for name, v in rows.items():
        print(f"  {name:20s} {min(v):.2e} / {np.median(v):.2e} / "
              f"{max(v):.2e}")
    return rows


def test_the_honest_program_reads_far_below_the_limit(table):
    """The absorbed program, chunked and through the pools, reads
    float32 rounding here on every one of the twelve seeds
    (``LOGITS_TOL`` is set from the chip's readings: the reference
    module says how)."""
    assert len(table["program"]) >= 12
    assert max(table["program"]) < 1e-4 < ref.LOGITS_TOL


@pytest.mark.parametrize("reading", ("bfloat16",) + ref.OMISSIONS)
def test_a_lower_precision_or_a_left_out_mechanism_fails_the_limit(
        table, reading):
    """What the limit has to catch: the reference computed in bfloat16;
    no index (every key attended), the most recent ``index_topk`` keys
    in the index's place, the index without its rotation, its key norm,
    its ``relu`` or its head weights, a top-k of half ``index_topk``; the
    latent un-normed, no rotation of the shared key; the selection bias
    off, the shared expert off, ``routed_scale`` 1 — each moves the worst
    checked row past ``LOGITS_TOL`` on every seed."""
    assert min(table[reading]) > ref.LOGITS_TOL, (reading, table[reading])

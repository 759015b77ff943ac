"""The limit on the logits of a served Solar Open 2 model
(``benchmark/reference/solar_open2.py`` ``LOGITS_TOL``), and the table
that proves it at a small size on the CPU: the honest program over
sixteen seeds far below it; the reference computed in bfloat16 and the
reference with each mechanism of ``OMISSIONS`` left out above it on
every seed.  (The same table at the published widths, on the chip, is
in PERF.md section 6, PR 31.)"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.solar_open2 import SolarOpen2ForCausalLM

from benchmark.reference import solar_open2 as ref
from test_solar_open2_serving import (VOCAB, _Step, _config, _reference,
                                      _reseed, _worst)

_SEEDS = range(16)


@pytest.fixture(scope="module")
def table():
    """Each reading of the table over ``_SEEDS``: the worst of the ten
    checked rows (the prompt's last and nine decode steps) of a 96-token
    sequence, as a share of the row's largest reference logit.  One
    model, one traced step and one compiled reference a variant serve
    every seed."""
    paddle.seed(0)
    m = SolarOpen2ForCausalLM(_config())
    m.eval()
    jitted = jax.jit(m.build_ragged_decode_step()[1])
    rows = {name: [] for name in ("program", "bfloat16") + ref.OMISSIONS}
    for seed in _SEEDS:
        _reseed(m, 100 + seed)
        seq = np.random.RandomState(seed).randint(0, VOCAB, (96,))
        want = _reference(m, seq)
        got = _Step(m, [seq], jitted=jitted, width=64).run([87], 40)[0]
        rows["program"].append(_worst(got, want))
        checked = dict.fromkeys(got)
        variants = [("bfloat16", dict(dtype=jnp.bfloat16))] \
            + [(o, dict(omit=(o,))) for o in ref.OMISSIONS]
        for name, change in variants:
            other = _reference(m, seq, **change)
            rows[name].append(_worst({p: other[p] for p in checked}, want))
    print("\nlogits error over", len(_SEEDS), "seeds (min / median / max):")
    for name, v in rows.items():
        print(f"  {name:16s} {min(v):.2e} / {np.median(v):.2e} / "
              f"{max(v):.2e}")
    return rows


def test_the_honest_program_reads_far_below_the_limit(table):
    """The program, chunked and through the state, reads float32
    rounding here on every one of the sixteen seeds (``LOGITS_TOL`` is
    set from the chip's readings: the reference module says how)."""
    assert len(table["program"]) >= 16
    assert max(table["program"]) < 1e-4 < ref.LOGITS_TOL


@pytest.mark.parametrize("reading", ("bfloat16",) + ref.OMISSIONS)
def test_a_lower_precision_or_a_left_out_mechanism_fails_the_limit(
        table, reading):
    """What the limit has to catch: the reference computed in bfloat16,
    and the reference without the decay, with the write strength not
    doubled, without the convolution, the q/k normalisation, the output
    gate, the GQA gate, the shared expert or the selection bias, each
    move the worst checked row past ``LOGITS_TOL`` on every seed."""
    assert min(table[reading]) > ref.LOGITS_TOL, (reading, table[reading])

"""SOT-lite: graph-break fallback for @to_static (ref: jit/sot/).

The VERDICT r3 'done' bar: a function with a host-dependent branch runs
under @to_static with BOTH branches exercised and parity vs eager.
"""
import warnings

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.jit import to_static
from paddle_tpu.jit import sot_lite


def _fn_with_branch(x):
    """Host-dependent control flow: bool() on a tensor is a graph break."""
    y = x * 2.0
    if (y.mean() > 0.0):          # Tensor.__bool__ → host read → break
        z = y + 10.0
    else:
        z = y - 10.0
    return z * 3.0


def test_both_branches_parity_vs_eager():
    fn = to_static(_fn_with_branch)
    pos = paddle.to_tensor(np.full((4,), 2.0, np.float32))
    neg = paddle.to_tensor(np.full((4,), -2.0, np.float32))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        out_pos = fn(pos)
        out_neg = fn(neg)
    np.testing.assert_allclose(out_pos.numpy(),
                               _fn_with_branch(pos).numpy())
    np.testing.assert_allclose(out_neg.numpy(),
                               _fn_with_branch(neg).numpy())
    # both guard paths are cached as separate specializations
    sot = next(iter(fn._sot_cache.values()))
    assert len(sot.traces) == 2
    # replays hit the compiled chains (same guard values) — outputs match
    out_pos2 = fn(paddle.to_tensor(np.full((4,), 2.0, np.float32)))
    np.testing.assert_allclose(out_pos2.numpy(), out_pos.numpy())


def test_segments_are_compiled_and_reused():
    calls = {"n": 0}

    def counted(x):
        calls["n"] += 1
        n = int((x.sum() > 0))      # int() host read → graph break
        return x * (n + 1)

    fn = to_static(counted)
    x = paddle.to_tensor(np.ones((3,), np.float32))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        a = fn(x)       # trace attempt (1) + SOT recording run (2)
        n_after_first = calls["n"]
        b = fn(x)       # replay: python body NOT re-executed
    assert calls["n"] == n_after_first
    np.testing.assert_allclose(a.numpy(), b.numpy())
    np.testing.assert_allclose(a.numpy(), 2.0 * np.ones(3))


def test_item_read_value_guard_respecialises():
    def f(x):
        s = float(x.max())          # .item()-style host read
        return x / max(s, 1.0)

    fn = to_static(f)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        a = fn(paddle.to_tensor(np.array([1.0, 4.0], np.float32)))
        b = fn(paddle.to_tensor(np.array([1.0, 8.0], np.float32)))
    np.testing.assert_allclose(a.numpy(), [0.25, 1.0])
    np.testing.assert_allclose(b.numpy(), [0.125, 1.0])


def test_gradients_flow_across_segments():
    def f(x):
        h = x * x
        if (h.sum() > 0):           # break between two diff'able segments
            out = h * 3.0
        else:
            out = h * 5.0
        return out.sum()

    fn = to_static(f)
    x = paddle.to_tensor(np.array([1.0, 2.0], np.float32),
                         stop_gradient=False)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        loss = fn(x)
        loss.backward()
    # d/dx (3x^2) = 6x
    np.testing.assert_allclose(x.grad.numpy(), [6.0, 12.0], rtol=1e-6)
    # second call takes the replay path; grads must still flow
    x2 = paddle.to_tensor(np.array([3.0, 1.0], np.float32),
                          stop_gradient=False)
    fn(x2).backward()
    np.testing.assert_allclose(x2.grad.numpy(), [18.0, 6.0], rtol=1e-6)


def test_full_graph_true_keeps_legacy_fallback():
    def f(x):
        if (x.sum() > 0):
            return x + 1.0
        return x - 1.0

    fn = to_static(f, full_graph=True)
    x = paddle.to_tensor(np.ones((2,), np.float32))
    with pytest.warns(RuntimeWarning, match="fallback to eager"):
        out = fn(x)
    np.testing.assert_allclose(out.numpy(), 2.0 * np.ones(2))
    assert fn._broken


def test_guard_explosion_gives_up_gracefully():
    def f(x):
        s = float(x.sum())          # a value that changes every call
        return x + s

    fn = to_static(f)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        outs = []
        for i in range(sot_lite.MAX_TRACES_PER_SIG + 3):
            x = paddle.to_tensor(np.full((2,), float(i), np.float32))
            outs.append(fn(x).numpy())
    for i, o in enumerate(outs):
        np.testing.assert_allclose(o, np.full((2,), 3.0 * i), rtol=1e-6)
    sot = next(iter(fn._sot_cache.values()))
    assert sot.gave_up


def test_oversized_guard_stays_eager():
    def f(x):
        _ = x.numpy()               # leaks the full (big) tensor
        return x * 2.0

    fn = to_static(f)
    big = paddle.to_tensor(
        np.ones((sot_lite.MAX_GUARD_ELEMS + 1,), np.float32))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        out = fn(big)
        out2 = fn(big)
    np.testing.assert_allclose(out.numpy(), 2.0)
    np.testing.assert_allclose(out2.numpy(), 2.0)


def test_constant_output_survives_replay():
    """An output leaf never touched by an op (a constant built inside the
    function) must be retained for replays."""
    def f(x):
        if (x.sum() > 0):
            y = x * 2.0
        else:
            y = x * 4.0
        return y, paddle.to_tensor(np.float32(7.0))

    fn = to_static(f)
    x = paddle.to_tensor(np.ones((2,), np.float32))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        _, c1 = fn(x)
        _, c2 = fn(x)     # replay path
    assert c2 is not None
    np.testing.assert_allclose(c1.numpy(), 7.0)
    np.testing.assert_allclose(c2.numpy(), 7.0)


def test_rng_op_refuses_specialization():
    """Dropout inside a graph-broken function: replay would freeze the
    mask — the signature must stay eager (fresh masks each call)."""
    import paddle_tpu.nn.functional as F

    def f(x):
        h = F.dropout(x, 0.5, training=True)
        if (x.sum() > 0):
            return h * 2.0
        return h

    fn = to_static(f)
    x = paddle.to_tensor(np.ones((64,), np.float32))
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        a = fn(x)
        b = fn(x)
    assert any("RNG" in str(r.message) for r in rec)
    sot = next(iter(fn._sot_cache.values()))
    assert sot.gave_up and not sot.traces
    # eager each call → independent dropout masks
    assert not np.array_equal(a.numpy(), b.numpy())


def test_cached_traces_survive_give_up():
    """After the specialization cap, already-compiled guard paths keep
    replaying (only NEW recordings stop)."""
    body_runs = {"n": 0}

    def f(x):
        body_runs["n"] += 1
        s = float(x.sum())
        return x + s

    fn = to_static(f)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        for i in range(sot_lite.MAX_TRACES_PER_SIG + 2):
            fn(paddle.to_tensor(np.full((2,), float(i), np.float32)))
        sot = next(iter(fn._sot_cache.values()))
        assert sot.gave_up
        n_before = body_runs["n"]
        # guard value 0.0 was the FIRST specialization — must replay
        out = fn(paddle.to_tensor(np.full((2,), 0.0, np.float32)))
    np.testing.assert_allclose(out.numpy(), 0.0)
    assert body_runs["n"] == n_before


def test_layer_forward_sot():
    import paddle_tpu.nn as nn

    class M(nn.Layer):
        def __init__(self):
            super().__init__()
            self.fc = nn.Linear(4, 4)

        def forward(self, x):
            h = self.fc(x)
            if (h.mean() > 100.0):   # break inside a Layer.forward
                return h * 0.0
            return h + 1.0

    paddle.seed(0)
    m = M()
    fn = to_static(m.forward)
    x = paddle.to_tensor(np.ones((2, 4), np.float32))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        out = fn(x)
        out2 = fn(x)    # replay
    ref = m.fc(x) + 1.0
    np.testing.assert_allclose(out.numpy(), ref.numpy(), rtol=1e-6)
    np.testing.assert_allclose(out2.numpy(), ref.numpy(), rtol=1e-6)


def test_param_update_visible_in_replay():
    """Externals (params) are read live at replay time, not baked."""
    import paddle_tpu.nn as nn
    paddle.seed(1)
    m = nn.Linear(2, 2)

    def f(x):
        h = m(x)
        if (h.sum() > 1e9):
            return h * 0.0
        return h * 2.0

    fn = to_static(f)
    x = paddle.to_tensor(np.ones((1, 2), np.float32))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        fn(x)
        m.weight.set_value(paddle.zeros_like(m.weight))
        m.bias.set_value(paddle.ones_like(m.bias))
        out = fn(x)     # replay must see the new weights
    np.testing.assert_allclose(out.numpy(), 2.0 * np.ones((1, 2)))


def test_logged_scalar_guard_relaxes_with_flag():
    """With FLAGS_sot_relax_guards on, a host-read scalar that is ONLY
    logged must not re-record forever: the second record demonstrates
    the op stream is value-independent, the guard widens to shape-only,
    and every later call replays the compiled chain."""
    logged = []

    def f(x):
        h = x * 2.0
        logged.append(float(h.sum()))     # host read → graph break
        return h + 1.0

    fn = to_static(f)
    paddle.set_flags({"FLAGS_sot_relax_guards": True})
    try:
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            for i in range(6):
                x = paddle.to_tensor(np.full((3,), float(i), np.float32))
                np.testing.assert_allclose(
                    fn(x).numpy(), np.full((3,), 2.0 * i + 1.0),
                    rtol=1e-6)
    finally:
        paddle.set_flags({"FLAGS_sot_relax_guards": False})
    sot = next(iter(fn._sot_cache.values()))
    assert len(sot.traces) == 1, "relaxation should keep ONE trace"
    assert not sot.gave_up
    # python body ran only for the two recordings; replays skip it
    assert len(logged) == 2, logged


def test_branch_on_host_read_stays_sound_by_default():
    """Value guards are the SOUND default: a predicate branch on a host
    read must keep per-branch specializations — inputs that cross the
    threshold after two same-side observations still get the right
    branch (the unsoundness that keeps relaxation opt-in)."""
    def f(x):
        s = float(x.sum())
        return x * 2.0 if s > 0 else x * 3.0

    fn = to_static(f)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        a = fn(paddle.to_tensor(np.full((2,), -2.0, np.float32)))
        b = fn(paddle.to_tensor(np.full((2,), -1.0, np.float32)))
        c = fn(paddle.to_tensor(np.full((2,), 2.0, np.float32)))
    np.testing.assert_allclose(a.numpy(), [-6.0, -6.0])
    np.testing.assert_allclose(b.numpy(), [-3.0, -3.0])
    np.testing.assert_allclose(c.numpy(), [4.0, 4.0])  # crossed: x*2


def test_baked_scalar_still_respecialises():
    """Relaxation must NOT fire when the leaked value feeds computation:
    the probe replay reproduces the OLD constant, outputs differ, and a
    fresh specialization is recorded (value semantics preserved)."""
    def f(x):
        s = float(x.sum())
        return x + s                      # s is baked into the chain

    fn = to_static(f)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        for i in range(3):
            x = paddle.to_tensor(np.full((2,), float(i), np.float32))
            np.testing.assert_allclose(
                fn(x).numpy(), np.full((2,), 3.0 * i), rtol=1e-6)
    sot = next(iter(fn._sot_cache.values()))
    assert len(sot.traces) == 3           # one per distinct baked value


def test_sot_stats_surface():
    """paddle.jit.sot.stats() (VERDICT r4 weak 6): per-function break/
    specialization/fallback rates are queryable."""
    from paddle_tpu.jit import sot

    def statsprobe_fn(x):
        s = float(x.sum())                 # graph break
        return x * 2.0 if s > 0 else x * 3.0

    fn = to_static(statsprobe_fn)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        xp = paddle.to_tensor(np.full((2,), 1.0, np.float32))
        xn = paddle.to_tensor(np.full((2,), -1.0, np.float32))
        fn(xp)          # record spec 1
        fn(xn)          # guard miss -> record spec 2
        fn(xp)          # replay hit
    st = sot.stats()["statsprobe_fn"]
    assert st["signatures"] == 1
    assert st["records"] == 2
    assert st["replay_hits"] == 1
    assert st["guard_misses"] == 1
    assert st["graph_breaks"] == 2
    assert st["segments"] >= 2
    assert st["eager_fallbacks"] == 0


def test_sot_error_on_fallback_flag():
    """FLAGS_sot_error_on_fallback: a silent eager de-optimization
    (here: an RNG op during recording) raises with remediation text."""
    from paddle_tpu.jit import sot

    def rngfall_fn(x):
        s = float(x.sum())                 # graph break -> SOT path
        return x * 2.0 if s > 0 else paddle.nn.functional.dropout(x, 0.5)

    fn = to_static(rngfall_fn)
    paddle.set_flags({"FLAGS_sot_error_on_fallback": True})
    try:
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            with pytest.raises(RuntimeError,
                               match="while_loop|relax_guards"):
                fn(paddle.to_tensor(np.full((2,), -1.0, np.float32)))
    finally:
        paddle.set_flags({"FLAGS_sot_error_on_fallback": False})
    st = sot.stats()["rngfall_fn"]
    assert st["eager_fallbacks"] >= 1
    assert any("RNG" in r for r in st["fallback_reasons"])

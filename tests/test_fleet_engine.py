"""Fleet hybrid-parallel + SPMD engine tests.

Adopts the reference's loss-parity oracle (SURVEY.md §4: multi-rank vs
single-rank run must produce the same losses) on the 8-virtual-device CPU
mesh.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.optimizer as opt
from paddle_tpu.distributed import fleet
from paddle_tpu.distributed.communication.group import _reset_groups
from paddle_tpu.distributed.fleet.base.topology import _clear_hcg
from paddle_tpu.distributed.mesh import reset_mesh
from paddle_tpu.jit import train_step
from paddle_tpu.models import GPTForPretraining, gpt_config


def _fresh():
    reset_mesh()
    _reset_groups()
    _clear_hcg()


@pytest.fixture(autouse=True)
def _cleanup():
    _fresh()
    yield
    _fresh()


def _init_fleet(dp=1, mp=1, sharding=1, pp=1):
    s = fleet.DistributedStrategy()
    s.hybrid_configs = {"dp_degree": dp, "mp_degree": mp,
                        "sharding_degree": sharding, "pp_degree": pp}
    fleet.init(is_collective=True, strategy=s)
    return s


def _run_losses(n_steps=3, seed=7, **hybrid):
    _fresh()
    _init_fleet(**hybrid)
    paddle.seed(seed)
    cfg = gpt_config("tiny", hidden_dropout_prob=0.0,
                     attention_dropout_prob=0.0)
    model = GPTForPretraining(cfg)
    optimizer = opt.AdamW(learning_rate=1e-3,
                          parameters=model.parameters())
    step = train_step(model, model.loss_fn, optimizer)
    rs = np.random.RandomState(0)
    ids = rs.randint(0, cfg.vocab_size, (8, 32)).astype(np.int64)
    labels = rs.randint(0, cfg.vocab_size, (8, 32)).astype(np.int64)
    return [float(step(ids, labels)) for _ in range(n_steps)]


def test_engine_loss_decreases():
    losses = _run_losses(n_steps=4, dp=8)
    assert losses[-1] < losses[0]
    assert all(np.isfinite(losses))


def test_hybrid_loss_parity_dp_vs_mp():
    """The oracle: same seed, same data — dp8 and dp2×mp4 (+sharding)
    runs must match the same loss trajectory."""
    base = _run_losses(dp=8)
    hybrid = _run_losses(dp=2, mp=4)
    np.testing.assert_allclose(base, hybrid, rtol=2e-4)
    zero3 = _run_losses(dp=2, sharding=2, mp=2)
    np.testing.assert_allclose(base, zero3, rtol=2e-4)


def test_sequence_parallel_parity():
    base = _run_losses(dp=2, mp=4)
    _fresh()
    _init_fleet(dp=2, mp=4)
    paddle.seed(7)
    cfg = gpt_config("tiny", hidden_dropout_prob=0.0,
                     attention_dropout_prob=0.0, sequence_parallel=True)
    model = GPTForPretraining(cfg)
    optimizer = opt.AdamW(learning_rate=1e-3, parameters=model.parameters())
    step = train_step(model, model.loss_fn, optimizer)
    rs = np.random.RandomState(0)
    ids = rs.randint(0, cfg.vocab_size, (8, 32)).astype(np.int64)
    labels = rs.randint(0, cfg.vocab_size, (8, 32)).astype(np.int64)
    sp = [float(step(ids, labels)) for _ in range(3)]
    np.testing.assert_allclose(base, sp, rtol=2e-4)


def test_recompute_parity():
    base = _run_losses(dp=8)
    _fresh()
    _init_fleet(dp=8)
    paddle.seed(7)
    cfg = gpt_config("tiny", hidden_dropout_prob=0.0,
                     attention_dropout_prob=0.0, use_recompute=True)
    model = GPTForPretraining(cfg)
    optimizer = opt.AdamW(learning_rate=1e-3, parameters=model.parameters())
    step = train_step(model, model.loss_fn, optimizer)
    rs = np.random.RandomState(0)
    ids = rs.randint(0, cfg.vocab_size, (8, 32)).astype(np.int64)
    labels = rs.randint(0, cfg.vocab_size, (8, 32)).astype(np.int64)
    rc = [float(step(ids, labels)) for _ in range(3)]
    np.testing.assert_allclose(base, rc, rtol=2e-4)


def test_group_sharded_stage3():
    _init_fleet(dp=2, sharding=4)
    paddle.seed(3)
    cfg = gpt_config("tiny", hidden_dropout_prob=0.0,
                     attention_dropout_prob=0.0)
    model = GPTForPretraining(cfg)
    optimizer = opt.AdamW(learning_rate=1e-3, parameters=model.parameters())
    from paddle_tpu.distributed.fleet.meta_parallel.sharding import (
        group_sharded_parallel)
    model2, optimizer, _ = group_sharded_parallel(model, optimizer,
                                                  level="p_g_os")
    step = train_step(model, model.loss_fn, optimizer)
    rs = np.random.RandomState(0)
    ids = rs.randint(0, cfg.vocab_size, (8, 32)).astype(np.int64)
    labels = rs.randint(0, cfg.vocab_size, (8, 32)).astype(np.int64)
    losses = [float(step(ids, labels)) for _ in range(3)]
    assert losses[-1] < losses[0]


def test_fleet_api_surface():
    s = _init_fleet(dp=2, mp=2, sharding=2)
    hcg = fleet.get_hybrid_communicate_group()
    assert hcg.get_model_parallel_world_size() == 2
    assert hcg.get_data_parallel_world_size() == 2
    assert hcg.get_sharding_parallel_world_size() == 2
    assert hcg.get_pipe_parallel_world_size() == 1
    assert hcg.get_parallel_mode() == "TENSOR_PARALLEL"
    topo = hcg.topology
    assert topo.world_size() == 8
    coord = topo.get_coord(0)
    assert coord.data == 0 and coord.model == 0
    # dp auto-degree
    _fresh()
    s = fleet.DistributedStrategy()
    s.hybrid_configs = {"dp_degree": -1, "mp_degree": 4}
    fleet.init(is_collective=True, strategy=s)
    hcg = fleet.get_hybrid_communicate_group()
    assert hcg.get_data_parallel_world_size() == 2


def test_pipeline_layer_segmentation():
    from paddle_tpu.distributed.fleet import LayerDesc, PipelineLayer
    import paddle_tpu.nn as nn
    _init_fleet(dp=2, pp=4)
    descs = [LayerDesc(nn.Linear, 8, 8) for _ in range(8)]
    pl = PipelineLayer(layers=descs, loss_fn=lambda o, l: (o - l).square().mean())
    assert pl.segment_parts == [0, 2, 4, 6, 8]
    assert len(pl.stage_layers(0)) == 2
    x = paddle.to_tensor(np.random.randn(2, 8).astype(np.float32))
    y = pl(x)
    assert y.shape == [2, 8]


def test_engine_tuner_selects_a_mesh():
    """Engine.tune (ref: auto_parallel tuner): search (dp, sharding, mp)
    factorizations, score with the XLA cost model, install the winner —
    params restored between trials."""
    _fresh()
    import paddle_tpu.nn as nn
    from paddle_tpu.distributed.auto_parallel.engine import Engine
    from paddle_tpu.distributed.auto_parallel.strategy import Strategy

    paddle.seed(0)
    model = nn.Sequential(nn.Linear(16, 32), nn.ReLU(), nn.Linear(32, 8))
    w0 = model[0].weight.numpy().copy()
    o = opt.SGD(learning_rate=0.1, parameters=model.parameters())
    eng = Engine(model, loss=lambda out, y: ((out - y) ** 2).mean(),
                 optimizer=o, strategy=Strategy())
    rs = np.random.RandomState(0)
    x = rs.randn(8, 16).astype(np.float32)
    y = rs.randn(8, 8).astype(np.float32)
    got = eng.tune(x, y, candidates=[(8, 1, 1), (2, 2, 2), (1, 1, 8)])
    assert {"dp", "sharding", "mp", "report"} <= set(got)
    assert got["dp"] * got["sharding"] * got["mp"] == 8
    assert len(eng.tuning_report) == 3
    scored = [e for e in eng.tuning_report if "score" in e]
    assert scored, eng.tuning_report
    # trial steps must not have trained the model
    np.testing.assert_array_equal(model[0].weight.numpy(), w0)
    # and the engine trains under the winning mesh afterwards
    from paddle_tpu.io import TensorDataset
    ds = TensorDataset([paddle.to_tensor(x), paddle.to_tensor(y)])
    hist = eng.fit(ds, batch_size=8, epochs=1)
    assert np.isfinite(hist["loss"]).all()


def test_engine_tune_warm_cache_zero_trial_steps(tmp_path):
    """Persistent plan cache (FLAGS_tuning_cache_dir): a second Engine
    over the same (model, batch, candidates, devices) resolves the
    search entirely from disk — zero trial steps, proven by the cache's
    hit/miss counters and a poisoned TrainStep."""
    import sys
    import jax
    import paddle_tpu.nn as nn
    from paddle_tpu.distributed.auto_parallel.engine import Engine
    from paddle_tpu.distributed.auto_parallel.strategy import Strategy
    from paddle_tpu.tuning import cache as tcache_mod

    _fresh()
    paddle.set_flags({"FLAGS_tuning_cache_dir": str(tmp_path)})
    try:
        paddle.seed(0)
        model = nn.Sequential(nn.Linear(16, 32), nn.ReLU(),
                              nn.Linear(32, 8))
        o = opt.SGD(learning_rate=0.1, parameters=model.parameters())
        loss = lambda out, y: ((out - y) ** 2).mean()   # noqa: E731
        rs = np.random.RandomState(0)
        x = rs.randn(8, 16).astype(np.float32)
        y = rs.randn(8, 8).astype(np.float32)
        cands = [(8, 1, 1), (2, 2, 2), (1, 1, 8)]

        eng = Engine(model, loss=loss, optimizer=o, strategy=Strategy())
        got = eng.tune(x, y, candidates=cands)
        st = tcache_mod.get_cache().stats()["engine_plan"]
        assert st["stores"] == 1 and st["misses"] == 1
        assert "cached" not in got

        # fresh-process stand-in: new cache instance, new Engine, and a
        # TrainStep that detonates if any trial step gets built
        _fresh()
        tcache_mod._active = None
        ts_mod = sys.modules["paddle_tpu.jit.train_step"]
        orig_ts = ts_mod.TrainStep

        def _poisoned(*a, **kw):
            raise AssertionError("trial step built despite a warm "
                                 "plan cache")

        ts_mod.TrainStep = _poisoned
        try:
            eng2 = Engine(model, loss=loss, optimizer=o,
                          strategy=Strategy())
            got2 = eng2.tune(x, y, candidates=cands)
        finally:
            ts_mod.TrainStep = orig_ts
        assert got2["cached"] is True
        assert (got2["dp"], got2["sharding"], got2["mp"]) == \
            (got["dp"], got["sharding"], got["mp"])
        st2 = tcache_mod.get_cache().stats()["engine_plan"]
        assert st2["hits"] == 1 and st2["misses"] == 0
        # the replayed report carries the ORIGINAL measurements plus an
        # explicit hit marker (no new step_s could exist — TrainStep is
        # poisoned above)
        assert eng2.tuning_report[-1]["cache"] == "hit"
        # the cached entry carries the canonical layout table
        rec = next(iter(tcache_mod.get_cache().entries("engine_plan")))
        assert rec["value"]["layout"]["mesh_axes"] == {
            "dp": got["dp"], "sharding": got["sharding"],
            "mp": got["mp"]}
        # and the engine still trains under the installed winner mesh
        from paddle_tpu.io import TensorDataset
        ds = TensorDataset([paddle.to_tensor(x), paddle.to_tensor(y)])
        hist = eng2.fit(ds, batch_size=8, epochs=1)
        assert np.isfinite(hist["loss"]).all()
    finally:
        paddle.set_flags({"FLAGS_tuning_cache_dir": ""})
        tcache_mod._active = None


def test_strategy_dict_config_merges_tuning():
    from paddle_tpu.distributed.auto_parallel.strategy import (
        Strategy, TuningConfig)
    s = Strategy({"tuning": {"enable": True, "profile": True}})
    assert isinstance(s.tuning, TuningConfig)
    assert s.tuning.enable and s.tuning.profile
    assert s.tuning.candidates is None     # unspecified keys keep defaults


def test_engine_tune_profile_topk_budget():
    """tune(profile=True, top_k, budget_s) (VERDICT r4 item 9): the
    roofline pre-rank limits MEASURED candidates to top_k, profile mode
    takes a multi-rep median, the budget stops new candidates without
    interrupting in-flight work, and pre-rank skips are reported."""
    _fresh()
    import paddle_tpu.nn as nn
    from paddle_tpu.distributed.auto_parallel.engine import Engine
    from paddle_tpu.distributed.auto_parallel.strategy import Strategy

    paddle.seed(1)
    model = nn.Sequential(nn.Linear(16, 32), nn.ReLU(), nn.Linear(32, 8))
    o = opt.SGD(learning_rate=0.1, parameters=model.parameters())
    eng = Engine(model, loss=lambda out, y: ((out - y) ** 2).mean(),
                 optimizer=o, strategy=Strategy())
    rs = np.random.RandomState(0)
    x = rs.randn(8, 16).astype(np.float32)
    y = rs.randn(8, 8).astype(np.float32)
    got = eng.tune(x, y, candidates=[(8, 1, 1), (4, 2, 1), (2, 2, 2),
                                     (1, 1, 8)],
                   profile=True, top_k=2)
    assert got["dp"] * got["sharding"] * got["mp"] == 8
    measured = [e for e in eng.tuning_report if "step_s" in e]
    skipped = [e for e in eng.tuning_report
               if e.get("skipped", "").startswith("below top_k")]
    assert len(measured) == 2, eng.tuning_report
    assert len(skipped) == 2, eng.tuning_report

    # zero budget: the first candidate still runs (a winner must
    # exist), later ones are skipped by budget
    _fresh()
    eng2 = Engine(model, loss=lambda out, y: ((out - y) ** 2).mean(),
                  optimizer=o, strategy=Strategy())
    got2 = eng2.tune(x, y, candidates=[(8, 1, 1), (2, 2, 2), (1, 1, 8)],
                     budget_s=0.0)
    budget_skips = [e for e in eng2.tuning_report
                    if e.get("skipped") == "tuning budget exhausted"]
    assert len(budget_skips) == 2, eng2.tuning_report
    assert got2["dp"] * got2["sharding"] * got2["mp"] == 8

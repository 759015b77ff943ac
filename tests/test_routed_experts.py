"""One chip's share of an expert layer (``ops/routed_experts.py``): the
shares of all chips add up to the whole layer, and no row is dropped."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import routed_experts
from paddle_tpu.ops.routed_experts import (held_experts_swiglu,
                                           sigmoid_topk_route)

H, I, E, K = 32, 16, 16, 2


def _layer(rs):
    return {"router_w": rs.randn(H, E).astype("float32"),
            "router_b": rs.uniform(-0.5, 0.5, (E,)).astype("float32"),
            "wg": rs.randn(E, H, I).astype("float32") * 0.2,
            "wu": rs.randn(E, H, I).astype("float32") * 0.2,
            "wd": rs.randn(E, I, H).astype("float32") * 0.2}


def _whole_layer(h, lp):
    """The uncut layer, written out: every expert over every row."""
    g = 1.0 / (1.0 + np.exp(-(h @ lp["router_w"])))
    picked = np.argsort(-(g + lp["router_b"]), axis=-1, kind="stable")[:, :K]
    y = np.zeros_like(h)
    for t in range(h.shape[0]):
        total = g[t, picked[t]].sum()
        for e in picked[t]:
            a = h[t] @ lp["wg"][e]
            y[t] += g[t, e] / total * (
                (a / (1.0 + np.exp(-a)) * (h[t] @ lp["wu"][e]))
                @ lp["wd"][e])
    return y, picked


def _share(h, lp, first, count, valid=None):
    with jax.default_matmul_precision("highest"):
        ids, w = sigmoid_topk_route(jnp.asarray(h), lp["router_w"],
                                    lp["router_b"], K)
        valid = jnp.ones((h.shape[0],), bool) if valid is None else valid
        # a fresh function a call: the tile is read when it is traced
        y, rows = jax.jit(lambda *a: held_experts_swiglu(*a, first))(
            jnp.asarray(h), ids, w, valid, lp["wg"][first:first + count],
            lp["wu"][first:first + count], lp["wd"][first:first + count])
    return np.asarray(y), np.asarray(rows), np.asarray(ids)


@pytest.fixture
def tile_of_8(monkeypatch):
    """Tiles of 8 rows, so that an expert's rows span several."""
    monkeypatch.setattr(routed_experts, "_TILE_ROWS", 8)


@pytest.mark.parametrize("tile", [256, 8])
def test_the_shares_of_all_chips_add_up_to_the_whole_layer(
        rng, tile, monkeypatch):
    monkeypatch.setattr(routed_experts, "_TILE_ROWS", tile)
    """The share test: four chips of four experts each.  With a tile of
    8 an expert's rows span several tiles."""
    lp = _layer(rng)
    h = rng.randn(40, H).astype("float32")
    want, picked = _whole_layer(h, lp)
    total, rows_seen = np.zeros_like(h), 0
    for first in range(0, E, 4):
        y, rows, ids = _share(h, lp, first, 4)
        np.testing.assert_array_equal(np.sort(ids, -1), np.sort(picked, -1))
        for e in range(4):
            assert rows[e] == (picked == first + e).sum()
        # a row none of whose picks live here gets exactly zero
        elsewhere = ~((picked >= first) & (picked < first + 4)).any(-1)
        assert elsewhere.any() and not y[elsewhere].any()
        total += y
        rows_seen += rows.sum()
    assert rows_seen == 40 * K                      # no row dropped
    np.testing.assert_allclose(total, want, atol=2e-5)


def test_all_rows_to_one_expert_lose_no_row(rng, tile_of_8):
    """A batch that routes every row to the same two experts: no
    capacity, so expert 5 takes all 64 rows (eight tiles of 8)."""
    lp = _layer(rng)
    lp["router_b"] = np.full((E,), -4.0, "float32")
    lp["router_b"][[5, 6]] = 4.0
    h = rng.randn(64, H).astype("float32")
    want, picked = _whole_layer(h, lp)
    assert set(picked.ravel()) == {5, 6}
    y, rows, _ = _share(h, lp, 4, 4)
    assert rows.tolist() == [0, 64, 64, 0]
    np.testing.assert_allclose(y, want, atol=2e-5)


def test_routing_ties_go_to_the_lower_id_and_lose_no_row(rng):
    """Identical router columns: every score ties, the two lowest ids
    win for every row, and the weights are halves."""
    lp = _layer(rng)
    lp["router_w"] = np.tile(lp["router_w"][:, :1], (1, E))
    lp["router_b"] = np.zeros((E,), "float32")
    h = rng.randn(24, H).astype("float32")
    ids, w = sigmoid_topk_route(jnp.asarray(h), lp["router_w"],
                                lp["router_b"], K)
    assert np.asarray(ids).tolist() == [[0, 1]] * 24
    np.testing.assert_allclose(np.asarray(w), 0.5, atol=1e-6)
    want, _ = _whole_layer(h, lp)
    y, rows, _ = _share(h, lp, 0, 4)
    assert rows.tolist() == [24, 24, 0, 0]
    np.testing.assert_allclose(y, want, atol=2e-5)


def test_padding_rows_route_nowhere(rng):
    lp = _layer(rng)
    h = rng.randn(16, H).astype("float32")
    valid = jnp.asarray(np.arange(16) < 10)
    y, rows, ids = _share(h, lp, 0, E, valid=valid)
    want, _ = _whole_layer(h[:10], lp)
    assert rows.sum() == 10 * K and not y[10:].any()
    np.testing.assert_allclose(y[:10], want, atol=2e-5)


def test_the_selection_bias_steers_the_pick_and_not_the_weight(rng):
    lp = _layer(rng)
    h = rng.randn(12, H).astype("float32")
    ids, w = sigmoid_topk_route(jnp.asarray(h), lp["router_w"],
                                lp["router_b"], K)
    g = 1.0 / (1.0 + np.exp(-(h @ lp["router_w"])))
    picked = np.take_along_axis(g, np.asarray(ids), -1)
    np.testing.assert_allclose(np.asarray(w),
                               picked / picked.sum(-1, keepdims=True),
                               atol=1e-6)
    plain, _ = sigmoid_topk_route(jnp.asarray(h), lp["router_w"],
                                  jnp.zeros((E,)), K)
    assert (np.sort(np.asarray(plain), -1)
            != np.sort(np.asarray(ids), -1)).any()

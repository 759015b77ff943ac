"""One chip's share of an expert layer (``ops/routed_experts.py``): the
shares of all chips add up to the whole layer, and no row is dropped."""
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.flags import get_flags, set_flags
from paddle_tpu.ops import routed_experts
from paddle_tpu.ops.routed_experts import (held_experts_swiglu,
                                           narrow_step, sigmoid_topk_route)

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


# ---------------------------------------------------------------------------
# the narrow step: a taken branch is the kernel that reads the expert's
# weights itself (ops/pallas/expert_swiglu.py, interpret mode here)
# ---------------------------------------------------------------------------

@pytest.fixture
def interpret():
    """Run a body with ``FLAGS_pallas_interpret`` as given: on, a narrow
    step takes the kernel path on this CPU; off, XLA's."""
    keep = get_flags(["FLAGS_pallas_interpret"])

    def under(flag: bool, body):
        set_flags({"FLAGS_pallas_interpret": flag})
        try:
            return body()
        finally:
            set_flags(keep)
    return under


def _rehearsal_widths(config: str):
    """``(T, hidden, expert width, held, router width, top_k)`` of a
    benchmark configuration's rehearsal: its decode-only step."""
    with open(os.path.join(os.path.dirname(__file__), os.pardir,
                           "benchmark", "configs", config + ".json")) as fh:
        r = json.load(fh)["rehearse"]
    return dict(t=8, h=r["hidden_size"], i=r["moe_intermediate_size"],
                count=r["n_routed_experts"], e=r["n_router_outputs"],
                k=r["num_experts_per_tok"], first=0)


# t rows of h wide, experts i wide, `count` held from `first` of e, top k;
# `bias`: the selection bias by expert id, `valid`: rows that carry a token
_NARROW = {
    "one_row": dict(t=1, bias={5: 9.0}),
    "eight_rows": dict(t=8),
    "twenty_four_rows": dict(t=24),
    "an_expert_with_no_row": dict(t=8, bias={5: -9.0}),
    "an_expert_given_every_row": dict(t=8, bias={5: 9.0, 6: 9.0}),
    "held_from_expert_twelve": dict(t=8, first=12),
    "padding_rows": dict(t=8, valid=5),
    "three_blocks_of_columns": dict(t=8, h=128, i=384, e=8, first=0,
                                    count=8),
    "solar_open2_rehearsal": _rehearsal_widths("solar-open2-8l-ep32"),
    "mimo_v2_5_rehearsal": _rehearsal_widths("mimo-v2.5-7l-ep32"),
    "glm_5_rehearsal": _rehearsal_widths("glm-5-5l-ep32"),
}


@pytest.mark.parametrize("case", sorted(_NARROW))
def test_the_kernel_path_is_the_xla_path_and_a_float64_brute_force(
        rng, interpret, case):
    """A narrow step through the kernel (interpret mode), through XLA's
    products and written out in float64: the same share, to 1e-5 of its
    largest entry."""
    c = dict(dict(h=H, i=I, e=E, k=K, first=4, count=4, bias={},
                  valid=None), **_NARROW[case])
    t, h_, i_, first, count = c["t"], c["h"], c["i"], c["first"], c["count"]
    lp = {"router_w": rng.randn(h_, c["e"]).astype("float32"),
          "router_b": np.zeros((c["e"],), "float32"),
          "wg": rng.randn(c["e"], h_, i_).astype("float32") * 0.2,
          "wu": rng.randn(c["e"], h_, i_).astype("float32") * 0.2,
          "wd": rng.randn(c["e"], i_, h_).astype("float32") * 0.2}
    for e, b in c["bias"].items():
        lp["router_b"][e] = b
    h = rng.randn(t, h_).astype("float32")
    valid = np.arange(t) < (t if c["valid"] is None else c["valid"])
    assert routed_experts._TILE_ROWS >= t * c["k"]

    def share():
        with jax.default_matmul_precision("highest"):
            ids, w = sigmoid_topk_route(jnp.asarray(h), lp["router_w"],
                                        lp["router_b"], c["k"])
            y, rows = jax.jit(lambda *a: held_experts_swiglu(*a, first))(
                jnp.asarray(h), ids, w, jnp.asarray(valid),
                tuple(lp["wg"][first:first + count]),
                tuple(lp["wu"][first:first + count]),
                tuple(lp["wd"][first:first + count]))
        return (np.asarray(y), np.asarray(rows), np.asarray(ids),
                np.asarray(w))

    kernel, rows, ids, w = interpret(True, share)
    xla, xla_rows, _, _ = interpret(False, share)
    want = np.zeros((t, h_))
    for r in np.flatnonzero(valid):
        for e, we in zip(ids[r], w[r]):
            if first <= e < first + count:
                a = h[r].astype("float64") @ lp["wg"][e]
                want[r] += we * ((a / (1.0 + np.exp(-a))
                                  * (h[r].astype("float64") @ lp["wu"][e]))
                                 @ lp["wd"][e])
    held = (ids >= first) & (ids < first + count) & valid[:, None]
    assert rows.tolist() == xla_rows.tolist() == [
        int((held & (ids == first + e)).sum()) for e in range(count)]
    if case == "an_expert_with_no_row":
        assert rows[5 - first] == 0
    if case == "an_expert_given_every_row":
        assert rows[5 - first] == rows[6 - first] == t
    if c["valid"] is not None:
        assert not kernel[c["valid"]:].any()
    assert want.any() and not kernel[~held.any(-1)].any()
    scale = np.abs(want).max()
    assert np.abs(kernel - want).max() <= 1e-5 * scale
    assert np.abs(xla - want).max() <= 1e-5 * scale
    assert np.abs(kernel - xla).max() <= 1e-5 * scale


def _traced(t, k, count=4):
    f32 = lambda *shape: jnp.zeros(shape, jnp.float32)
    args = (f32(t, H), jnp.zeros((t, k), jnp.int32), f32(t, k),
            jnp.ones((t,), bool), (f32(H, I),) * count,
            (f32(H, I),) * count, (f32(I, H),) * count)
    return jax.jit(lambda *a: held_experts_swiglu(*a, 0)), args


@pytest.mark.parametrize("t,k,kernel", [(8, 8, True), (32, 8, True),
                                        (128, 2, True), (40, 8, False),
                                        (128, 4, False), (1032, 8, False)])
def test_shapes_alone_send_a_step_down_the_kernel_or_the_xla_path(
        interpret, t, k, kernel):
    """``T * k <= 256`` pairs are one tile: the decode-only program's 8
    rows of 8 picks take the kernel, a 128-row step of 4 picks (the
    narrowest step with a chunk, at any top-k over 2) and a chunk's 1,032
    rows take XLA's loop over tiles.  Without the kernel (a CPU out of
    interpret mode) every step takes XLA's."""
    for flag in (True, False):
        fn, args = _traced(t, k)      # the route is read when it is traced
        assert interpret(flag, lambda: narrow_step(t, k)) \
            == (kernel and flag)
        traced = interpret(flag, lambda: str(jax.make_jaxpr(fn)(*args)))
        assert ("pallas_call" in traced) == (kernel and flag)


@pytest.mark.parametrize("count", [4, 10])
def test_a_narrow_step_still_holds_one_conditional_a_held_expert(
        interpret, count):
    """The benchmark's readers find the expert layer's device seconds by
    its ``conditional`` operations, one a held expert, whose output is
    the layer's ``[rows, hidden]``, under the scope ``expert_matmul``."""
    fn, args = _traced(8, 8, count)
    text = interpret(True, lambda: fn.lower(*args).as_text(debug_info=True))
    # the end of a case whose one output is [8 rows, hidden] (the
    # interpreted kernel's own `pl.when` is a case too, of other outputs)
    ends = [line for line in text.splitlines()
            if re.match(rf"\s*\}}\) : \(tensor<i32>\) -> tensor<8x{H}xf32>",
                        line)]
    assert len(ends) == count, text[-3000:]
    assert text.count("expert_matmul") >= count

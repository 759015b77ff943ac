"""The ragged kernel's window, sink and value width: the Pallas kernel in
interpret mode against ``ragged_paged_attention_ref``, and the reference
against attention written out densely."""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.flags import get_flags, set_flags
from paddle_tpu.ops.pallas import ragged_paged_attention as rpa

WINDOW, PS, CHUNK = 8, 4, 6


@pytest.fixture
def interpret():
    keep = get_flags(["FLAGS_pallas_interpret",
                      "FLAGS_use_pallas_ragged_attention"])
    set_flags({"FLAGS_pallas_interpret": True})
    yield
    set_flags(keep)


def _case(rs, kv_lens, q_lens, qw, ring, nh=4, nkv=2, hd=24, hdv=16):
    """Pools filled by writing every position of every lane where the
    page table (a ring, or a plain table) puts it; a ring keeps the last
    ``R * PS`` positions only."""
    b = len(kv_lens)
    ppseq = (-(-(WINDOW + CHUNK) // PS) + 1) if ring \
        else -(-max(kv_lens) // PS)
    tables = np.arange(b * ppseq, dtype="int32").reshape(b, ppseq)
    k = np.zeros((nkv, b * ppseq + 1, PS, hd), "float32")
    v = np.zeros((nkv, b * ppseq + 1, PS, hdv), "float32")
    dense_k = [rs.randn(n, nkv, hd).astype("float32") for n in kv_lens]
    dense_v = [rs.randn(n, nkv, hdv).astype("float32") for n in kv_lens]
    for i, n in enumerate(kv_lens):
        for p in range(n):
            entry = (p // PS) % ppseq if ring else p // PS
            k[:, tables[i, entry], p % PS] = dense_k[i][p]
            v[:, tables[i, entry], p % PS] = dense_v[i][p]
    q = rs.randn(b, qw, nh, hd).astype("float32")
    return (jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
            jnp.asarray(kv_lens, jnp.int32), jnp.asarray(q_lens, jnp.int32),
            jnp.asarray(tables)), dense_k, dense_v


def _dense(q, dense_k, dense_v, kv_lens, q_lens, window, sinks):
    """Attention written out: one query row at a time."""
    b, qw, nh, hd = q.shape
    out = np.zeros((b, qw, nh, dense_v[0].shape[-1]), "float32")
    for i in range(b):
        rep = nh // dense_k[i].shape[1]
        for j in range(q_lens[i]):
            pos = kv_lens[i] - q_lens[i] + j
            lo = 0 if window is None else max(0, pos - window + 1)
            for h in range(nh):
                a = dense_k[i][lo:pos + 1, h // rep] @ q[i, j, h] \
                    / math.sqrt(hd)
                m = max(a.max(), sinks[h]) if sinks is not None else a.max()
                e = np.exp(a - m)
                den = e.sum() + (np.exp(sinks[h] - m)
                                 if sinks is not None else 0.0)
                out[i, j, h] = (e / den) @ dense_v[i][lo:pos + 1, h // rep]
    return out


# contexts below, at and far above the window; a decode step and a chunk
@pytest.mark.parametrize("context", [5, WINDOW, 3 * WINDOW + 1, 61])
@pytest.mark.parametrize("q_len", [1, CHUNK])
@pytest.mark.parametrize("sink", [False, True])
def test_window_kernel_matches_reference_over_a_ring(interpret, rng,
                                                     context, q_len, sink):
    kv_lens = [max(q_len, context), max(q_len, context - 3), context + 2]
    q_lens = [q_len, q_len, 0]                     # the last lane idles
    args, dk, dv = _case(rng, kv_lens, q_lens, q_len, ring=True)
    sinks = jnp.asarray(rng.randn(4), jnp.float32) if sink else None
    assert rpa.available()
    got = np.asarray(rpa.ragged_paged_attention(
        *args, window=WINDOW, sinks=sinks))
    ref = np.asarray(rpa.ragged_paged_attention_ref(
        *args, window=WINDOW, sinks=sinks))
    want = _dense(np.asarray(args[0]), dk, dv, kv_lens, q_lens, WINDOW,
                  None if sinks is None else np.asarray(sinks))
    assert got.shape == (3, q_len, 4, 16)            # the values' width
    for i in range(2):
        np.testing.assert_allclose(got[i], ref[i], atol=2e-6)
        np.testing.assert_allclose(ref[i], want[i], atol=2e-6)


@pytest.mark.parametrize("window", [None, WINDOW])
def test_values_narrower_than_keys_and_a_sink_over_a_plain_table(
        interpret, rng, window):
    """Full attention with a sink and values of another width, and a
    window over a table that holds the whole sequence (the ring that
    never wraps)."""
    kv_lens, q_lens = [29, 13], [CHUNK, 1]
    args, dk, dv = _case(rng, kv_lens, q_lens, CHUNK, ring=False)
    sinks = jnp.asarray(rng.randn(4) * 2.0, jnp.float32)
    got = np.asarray(rpa.ragged_paged_attention(
        *args, window=window, sinks=sinks))
    ref = np.asarray(rpa.ragged_paged_attention_ref(
        *args, window=window, sinks=sinks))
    want = _dense(np.asarray(args[0]), dk, dv, kv_lens, q_lens, window,
                  np.asarray(sinks))
    for i, n in enumerate(q_lens):
        np.testing.assert_allclose(got[i, :n], ref[i, :n], atol=2e-6)
        np.testing.assert_allclose(ref[i, :n], want[i, :n], atol=2e-6)


def test_a_window_layer_visits_nine_pages_at_any_context():
    """The one pass of a window layer: the pages that
    ``rows + W - 1`` consecutive positions can touch, whatever the
    context — 9 for a decode step at a window of 128 and pages of 16."""
    assert rpa._window_pages(1, 128, 16) == 9
    assert rpa._window_pages(8, 128, 16) == 10
    assert rpa._window_pages(1, 8, 4) == 3
    # a query at position 8191 (kv_len 8192, one row): its oldest key is
    # position 8064, on page 504
    assert int(rpa._first_page(jnp.int32(8192), jnp.int32(1), jnp.int32(0),
                               128, 16)) == 504
    assert int(rpa._first_page(jnp.int32(40), jnp.int32(1), jnp.int32(0),
                               128, 16)) == 0


def _paged(rs, kv_lens, ps, ppseq, nkv, hd, hdv):
    """Every lane's keys and values laid into pools through a plain
    table of ``ppseq`` entries a lane; entries past a lane's pages stay
    0, as the scheduler leaves them."""
    b = len(kv_lens)
    tables = np.zeros((b, ppseq), "int32")
    k = rs.randn(nkv, b * ppseq + 1, ps, hd).astype("float32")
    v = rs.randn(nkv, b * ppseq + 1, ps, hdv).astype("float32")
    dense_k, dense_v = [], []
    for i, n in enumerate(kv_lens):
        pages = -(-n // ps)
        tables[i, :pages] = 1 + i * ppseq + np.arange(pages)
        dense_k.append(rs.randn(n, nkv, hd).astype("float32"))
        dense_v.append(rs.randn(n, nkv, hdv).astype("float32"))
        for p in range(n):
            k[:, tables[i, p // ps], p % ps] = dense_k[i][p]
            v[:, tables[i, p // ps], p % ps] = dense_v[i][p]
    return k, v, tables, dense_k, dense_v


def _kernel_against_dense(rng, kv_lens, q_lens, qw, tables=None, ppseq=21,
                          window=None):
    ps, nh, nkv, hd, hdv = 16, 4, 2, 24, 16
    k, v, own, dk, dv = _paged(rng, kv_lens, ps, ppseq, nkv, hd, hdv)
    q = rng.randn(len(kv_lens), qw, nh, hd).astype("float32")
    got = np.asarray(rpa.ragged_paged_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
        jnp.asarray(kv_lens, jnp.int32), jnp.asarray(q_lens, jnp.int32),
        jnp.asarray(own if tables is None else tables), window=window))
    want = _dense(q, dk, dv, kv_lens, q_lens, window, None)
    for i, n in enumerate(q_lens):
        np.testing.assert_allclose(got[i, :n], want[i, :n], atol=3e-6)
    return got


# the causal walk takes a block of _KEYS_PER_STEP keys a turn: contexts
# inside one block of 128 or 256, at its edge, one key past it and over
# several; a decode step and a chunk of two query tiles beside a
# decoding and an idle lane; 21 entries, not a whole number of blocks
@pytest.mark.parametrize("context", [100, 128, 129, 256, 257, 300])
@pytest.mark.parametrize("q_len", [1, 132])
def test_causal_kernel_takes_several_pages_a_turn(interpret, rng,
                                                  context, q_len):
    assert q_len == 1 or q_len > rpa._block_q(4, 24, 4)
    _kernel_against_dense(rng, [max(q_len, context), 45, 0],
                          [q_len, 1, 0], q_len)


@pytest.mark.parametrize("q_len", [1, 40, 200])
def test_a_table_s_last_block_is_partial(interpret, rng, q_len):
    """21 entries of 16 keys, every one in use: the walk's last block
    holds the table's last pages and the entries the launch added to
    make whole blocks, which lie past the context."""
    assert (21 * 16) % rpa._KEYS_PER_STEP
    _kernel_against_dense(rng, [336, 330], [q_len, 1], q_len)


@pytest.mark.parametrize("blocks", [1, 2])
def test_a_context_that_ends_on_a_block_s_edge_beside_an_idle_lane(
        interpret, rng, blocks):
    """The last key is the last of its block: the walk takes ``blocks``
    turns and starts no copy of a block after it, and the idle lane
    after it starts none at all and reads exactly zero."""
    edge = blocks * rpa._KEYS_PER_STEP
    assert int(rpa._last_block(jnp.int32(edge), jnp.int32(1), jnp.int32(0),
                               8, rpa._KEYS_PER_STEP)) == blocks - 1
    got = _kernel_against_dense(rng, [edge, 0, edge + 1], [1, 0, 1], 1,
                                ppseq=40)
    assert np.all(got[1] == 0.0)


def test_two_lanes_whose_tables_name_the_same_pages(interpret, rng):
    """A prefix shared through the prefix cache: two lanes read the same
    first pages, each its own after them."""
    ps, kv_lens = 16, [200, 150]
    k, v, tables, dk, dv = _paged(rng, kv_lens, ps, 21, 2, 24, 16)
    shared = 6                                     # pages of 16 keys
    tables[1, :shared] = tables[0, :shared]
    dk[1][:shared * ps], dv[1][:shared * ps] = \
        dk[0][:shared * ps], dv[0][:shared * ps]
    q = rng.randn(2, 3, 4, 24).astype("float32")
    q_lens = [3, 1]
    got = np.asarray(rpa.ragged_paged_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
        jnp.asarray(kv_lens, jnp.int32), jnp.asarray(q_lens, jnp.int32),
        jnp.asarray(tables)))
    want = _dense(q, dk, dv, kv_lens, q_lens, None, None)
    for i, n in enumerate(q_lens):
        np.testing.assert_allclose(got[i, :n], want[i, :n], atol=3e-6)


def test_a_window_wider_than_one_pass_is_walked_in_blocks(interpret, rng):
    """A window that reaches more keys than one pass holds: blocks of
    ``_MAX_WINDOW_KEYS`` from the page of the oldest visible key."""
    window = rpa._MAX_WINDOW_KEYS + 100
    bq, group = rpa._tiling(1, 4, 24, 4, 16, 60, window)
    assert group * 16 == rpa._MAX_WINDOW_KEYS
    assert rpa.walk_blocks([900, 300, 0], [1, 1, 0], 1, 4, 24, 4, 16, 60,
                           window) == 2 + 1
    _kernel_against_dense(rng, [900, 300, 0], [1, 1, 0], 1, ppseq=60,
                          window=window)


@pytest.mark.parametrize("window", [None, 128])
@pytest.mark.parametrize("qw,kv_lens,q_lens", [
    (1, [2500, 129, 128, 1, 0], [1, 1, 1, 1, 0]),
    (1024, [2048, 700, 0, 4096], [1024, 1, 0, 1]),
    (300, [300, 77, 310], [300, 1, 130])])
def test_the_host_s_count_of_blocks_is_the_kernel_s_own_bounds(
        window, qw, kv_lens, q_lens):
    """``walk_blocks`` (numpy, what ``attn_blocks`` sums) against the
    bounds as the kernel takes them: ``_walk`` a lane and a tile in
    jnp, at the longgen cell's heads."""
    nh, hd, ps, ppseq = 64, 256, 16, 73 if window else 512
    bq, group = rpa._tiling(qw, nh, hd, 4, ps, ppseq, window)
    want = 0
    for kv, q in zip(kv_lens, q_lens):
        for q0 in range(0, -(-qw // bq) * bq, bq):
            first, blocks = rpa._walk(jnp.int32(kv), jnp.int32(q),
                                      jnp.int32(q0), bq, ps, group, window)
            want += int(blocks)
            if int(blocks) and window is None:
                assert int(blocks) == int(rpa._last_block(
                    jnp.int32(kv), jnp.int32(q), jnp.int32(q0), bq,
                    group * ps)) + 1
    assert want > 0
    assert rpa.walk_blocks(kv_lens, q_lens, qw, nh, hd, 4, ps, ppseq,
                           window) == want


def test_a_tile_s_walk_ends_at_the_block_of_its_last_row():
    """``_last_block``: where a tile's walk ends.  A decode
    row at position 2,499 ends in block 19 of 128 keys; the first tile
    of a 1,024-row chunk after 1,024 cached tokens ends in block 8, its
    last tile in block 15; a tile of padding rows stays on block 0."""
    def last(kv, q, q0, bq=8, keys=128):
        return int(rpa._last_block(jnp.int32(kv), jnp.int32(q),
                                   jnp.int32(q0), bq, keys))
    assert last(2500, 1, 0) == 19
    assert last(2048, 1024, 0) == 8
    assert last(2048, 1024, 1016) == 15
    assert last(2500, 1, 8) == 0          # rows past q_len
    assert last(0, 0, 0) == 0             # an idle lane
    assert last(128, 1, 0) == 0 and last(129, 1, 0) == 1


def test_query_tiles_fill_half_of_the_vmem_the_kernel_asks_for():
    """``_block_q`` at the three serve geometries: 64 heads of keys
    padded to 256 take tiles of 32 rows, 32 heads of 128 and 16 heads of
    96 the most a tile may be; a decode step keeps one tile of 8."""
    assert rpa._VMEM_TILE_BUDGET * 2 == rpa._VMEM_LIMIT == 64 << 20
    assert rpa._block_q(64, 256, 4) == 32
    assert rpa._block_q(32, 128, 4) == 128
    assert rpa._block_q(16, 96, 4) == 128


def _pallas_calls(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for value in eqn.params.values():
            inner = getattr(value, "jaxpr", None)
            if inner is not None:
                yield from _pallas_calls(getattr(inner, "jaxpr", inner))


# the serve cells' launches: heads, kv heads, key and value widths, pages,
# table entries, window, tile rows at Q=1024
@pytest.mark.parametrize("nh,nkv,hd,hdv,pages,ppseq,window,bq", [
    (32, 8, 128, 128, 2049, 256, None, 128),
    (64, 4, 256, 128, 4097, 512, None, 32),
    (64, 8, 256, 128, 585, 73, 128, 32),
    (64, 8, 128, 128, 4097, 512, None, 64)],
    ids=["batch", "longgen-full", "longgen-window", "longdoc"])
@pytest.mark.parametrize("qw", [1, 1024])
def test_the_grid_is_the_steps_live_tiles_and_the_pools_stay_in_hbm(
        nh, nkv, hd, hdv, pages, ppseq, window, bq, qw):
    """No page axis and no lane axis: one grid step a tile slot of the
    step's packed rows (8 for a decode step; ``ceil(1032 / bq) + 8`` for
    a 1,024-row chunk beside seven decoding lanes, where lanes x q tiles
    would be ``8 * 1024 / bq``), whatever the table's length, and one
    launch that takes each pool whole, in any memory space, beside q and
    the four prefetched arrays.  The output is ``[slots, nh, bq, hdv]``:
    4-dimensional with a tile's rows third, which is how the
    benchmark's readers tell a decode step's launch (``dims[2] <= 8``)
    from a wide one's."""
    f32, i32 = jnp.float32, jnp.int32
    sds = jax.ShapeDtypeStruct
    rows = 8 if qw == 1 else 1032
    jaxpr = jax.make_jaxpr(functools.partial(
        rpa._ragged_call, q_width=qw, scale=0.1, window=window,
        precision=None, interpret=False))(
        sds((rows, nh, hd), f32), sds((nkv, pages, 16, hd), f32),
        sds((nkv, pages, 16, hdv), f32), sds((8,), i32), sds((8,), i32),
        sds((8,), i32), sds((8, ppseq), i32), None)
    [call] = _pallas_calls(jaxpr.jaxpr)
    grid = call.params["grid_mapping"]
    slots = 8 if qw == 1 else -(-1032 // bq) + 8
    assert slots == {1: 8, 128: 17, 32: 41, 64: 25}[1 if qw == 1 else bq]
    assert grid.grid == (slots,)
    assert len(call.invars) == 7
    blocks = [str(b.transformed_block_aval) for b in grid.block_mappings]
    assert blocks[1] == f"Ref<any>{{float32[{nkv},{pages},16,{hd}]}}"
    assert blocks[2] == f"Ref<any>{{float32[{nkv},{pages},16,{hdv}]}}"
    assert call.outvars[0].aval.shape == (slots, nh, 8 if qw == 1 else bq,
                                          hdv)
    assert rpa.launch_tiles([1024] + [1] * 7 if qw > 1 else [1] * 8, rows,
                            qw, nh, hd, 4, 16, ppseq, window) \
        == (8 if qw == 1 else 1024 // bq + 7, slots)


# ---------------------------------------------------------------------------
# the packed launch: the grid is the step's live tiles
# ---------------------------------------------------------------------------

def _packed_against_reference(rng, before, q_lens, qw, n_rows, nh=4, nkv=2,
                              hd=24, hdv=16, window=None, sink=False):
    """``ragged_paged_attention_rows`` through the kernel on ``n_rows``
    packed rows — sequence ``b`` feeds ``q_lens[b]`` rows behind a
    context of ``before[b]`` keys — against ``ragged_paged_attention_ref``
    on the same rows laid out ``[B, Q]``."""
    ps, b = 16, len(q_lens)
    kv_lens = [c + n for c, n in zip(before, q_lens)]
    ppseq = -(-max(kv_lens) // ps) + 1
    k, v, tables, _, _ = _paged(rng, kv_lens, ps, ppseq, nkv, hd, hdv)
    q = rng.randn(n_rows, nh, hd).astype("float32")
    offs = np.cumsum(q_lens) - np.asarray(q_lens)
    sinks = jnp.asarray(rng.randn(nh) * 2.0, jnp.float32) if sink else None
    i32 = jnp.int32
    shared = (jnp.asarray(k), jnp.asarray(v), jnp.asarray(kv_lens, i32),
              jnp.asarray(q_lens, i32))
    assert rpa.available() and sum(q_lens) <= n_rows and max(q_lens) <= qw
    got = np.asarray(rpa.ragged_paged_attention_rows(
        jnp.asarray(q), *shared, jnp.asarray(offs, i32),
        jnp.asarray(tables), qw, window=window, sinks=sinks))
    assert got.shape == (n_rows, nh, hdv)
    lanes = np.zeros((b, qw, nh, hd), "float32")
    for i, n in enumerate(q_lens):
        lanes[i, :n] = q[offs[i]:offs[i] + n]
    want = np.asarray(rpa.ragged_paged_attention_ref(
        jnp.asarray(lanes), *shared, jnp.asarray(tables), window=window,
        sinks=sinks))
    for i, n in enumerate(q_lens):
        np.testing.assert_allclose(got[offs[i]:offs[i] + n], want[i, :n],
                                   atol=3e-6)
    # a row that carries no token is zero, whatever tile it sits behind
    assert np.all(got[sum(q_lens):] == 0.0)


# (context a lane, rows a lane, attention width, rows of the program) at
# 4 heads of 24 (tiles of up to 128 rows): slots = min(B * ceil(Q / bq),
# ceil(rows / bq) + B)
_PACKED_STEPS = {
    # one chunk between decoding lanes, as Scheduler.plan_step packs it
    "chunk-96-between-decodes": ([300, 9, 40, 77, 130], [1, 1, 96, 1, 1],
                                 128, 136),
    "chunk-1024-between-decodes": ([60, 70, 17], [1, 1024, 1], 1024, 1032),
    "two-short-chunks": ([33, 0, 250], [5, 1, 7], 8, 128),
    "an-empty-lane-in-the-middle": ([100, 500, 0, 40], [40, 0, 0, 13],
                                    64, 128),
    # 130 and 134 rows in tiles of 128: both lanes end inside a tile and
    # the second one's last six rows sit in the last slot
    "rows-straddle-the-last-slot": ([20, 300], [130, 134], 256, 264),
    "every-slot-live": ([129, 256, 7], [1, 1, 1], 1, 3),
    "one-slot-live": ([129, 256, 7], [0, 1, 0], 1, 3),
    "nothing-live": ([129, 256], [0, 0], 1, 2),
}


@pytest.mark.parametrize("window,sink", [(None, False), (40, True)],
                         ids=["causal", "window-sink"])
@pytest.mark.parametrize("name", sorted(_PACKED_STEPS))
def test_packed_rows_launch_matches_reference(interpret, rng, name, window,
                                              sink):
    before, q_lens, qw, n_rows = _PACKED_STEPS[name]
    bq, _ = rpa._tiling(qw, 4, 24, 4, 16, 99, window)
    live, slots = rpa.launch_tiles(q_lens, n_rows, qw, 4, 24, 4, 16, 99,
                                   window)
    assert live == sum(-(-n // bq) for n in q_lens) <= slots
    assert slots == min(len(q_lens) * -(-qw // bq),
                        -(-n_rows // bq) + len(q_lens))
    if name == "every-slot-live" or name == "rows-straddle-the-last-slot":
        assert live == slots
    if name == "chunk-1024-between-decodes":
        assert (live, slots) == (10, 12)           # lanes x q tiles: 24
    _packed_against_reference(rng, before, q_lens, qw, n_rows,
                              window=window, sink=sink)


# the longgen cell's heads (tiles of 32 rows) over 4 and 8 kv heads, keys
# of 192 in 256 and values of 128, q_lens that are no multiple of a tile
@pytest.mark.parametrize("nkv,window,sink", [(4, None, False),
                                             (8, 32, True)],
                         ids=["gqa16-causal", "gqa8-window-sink"])
def test_packed_rows_at_wide_heads_take_tiles_of_32(interpret, rng, nkv,
                                                    window, sink):
    q_lens, n_rows = [1, 70, 0, 1, 33], 112
    assert rpa.launch_tiles(q_lens, n_rows, 80, 64, 256, 4, 16, 40,
                            window) == (1 + 3 + 0 + 1 + 2, 4 + 5)
    _packed_against_reference(rng, [200, 90, 0, 17, 300], q_lens, 80,
                              n_rows, nh=64, nkv=nkv, hd=256, hdv=128,
                              window=window, sink=sink)


@pytest.mark.parametrize("window", [None, WINDOW])
@pytest.mark.parametrize("q_lens", [[CHUNK, 1, 0, 3], [CHUNK] * 4],
                         ids=["ragged", "every-lane-full"])
def test_the_lanes_call_is_the_packed_call_on_the_same_rows(
        interpret, rng, window, q_lens):
    """``ragged_paged_attention(q [B, Q])`` is the packed launch with
    sequence ``b``'s rows at ``b * Q``: bit for bit the same rows."""
    kv_lens = [29, 13, 5, 40]
    args, _, _ = _case(rng, kv_lens, q_lens, CHUNK, ring=False)
    q, k, v, kv, ql, tables = args
    sinks = jnp.asarray(rng.randn(4), jnp.float32)
    lanes = np.asarray(rpa.ragged_paged_attention(
        *args, window=window, sinks=sinks))
    rows = np.asarray(rpa.ragged_paged_attention_rows(
        q.reshape(4 * CHUNK, 4, 24), k, v, kv, ql,
        jnp.arange(4, dtype=jnp.int32) * CHUNK, tables, CHUNK,
        window=window, sinks=sinks))
    np.testing.assert_array_equal(lanes.reshape(rows.shape), rows)
    ref = np.asarray(rpa.ragged_paged_attention_ref(
        *args, window=window, sinks=sinks))
    for i, n in enumerate(q_lens):
        np.testing.assert_allclose(lanes[i, :n], ref[i, :n], atol=2e-6)
        assert np.all(lanes[i, n:] == 0.0)

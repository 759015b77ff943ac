"""What keeps the chip bring-up from rotting, checked without a chip:
``chip_smoke.py`` still runs end to end in its CPU rehearsal and still
refuses to produce a result without an accelerator; the compile cache
is placed by the one rule; an unknown device has no peak FLOPs."""
import json
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SMOKE = os.path.join(_REPO, "chip_smoke.py")


def _run(argv, env=None, timeout=600):
    return subprocess.run([sys.executable, *argv], capture_output=True,
                          text=True, timeout=timeout, cwd=_REPO,
                          env={**os.environ, **(env or {})})


def test_chip_smoke_rehearsal_is_green():
    """Tiny preset, Pallas interpret mode, both phases as children of a
    parent that never imports jax.  The last line is the result object
    with exactly the keys the chip check reads, and says it ran on the
    CPU; the line before it holds the phases' reports."""
    proc = _run([_SMOKE, "--rehearse"])
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    assert "platform: cpu" in proc.stdout
    report, last = proc.stdout.strip().splitlines()[-2:]
    out = json.loads(last)
    assert set(out) == {"ok", "device"} and out["ok"] is True
    assert set(out["device"]) == {"platform", "kind", "count"}
    assert out["device"]["platform"] == "cpu"
    assert isinstance(out["device"]["kind"], str)
    assert type(out["device"]["count"]) is int
    detail = json.loads(report.removeprefix("report: "))
    assert detail["rehearsal"] is True
    assert set(detail["phases"]) == {"train", "serve"}
    # counted on the CPU too, asserted only where a chip compiles it
    assert {"pool_copies_q1", "pool_copies_q1_hd128_layer",
            "pool_copies_q1_hd64_layer", "pool_copies_q1_key192_full_layer",
            "pool_copies_q1_key192_window_layer", "pool_copies_packed"} \
        <= set(detail["phases"]["serve"])
    # the packed step's guard: rows beside q_width, no product at 8 x Q
    serve = detail["phases"]["serve"]
    assert (serve["packed_q_width"], serve["packed_rows"]) == (64, 128)
    assert 128 in serve["packed_matmul_rows"] \
        and 512 not in serve["packed_matmul_rows"]


def test_chip_smoke_without_a_chip_fails_and_prints_no_result():
    proc = _run([_SMOKE], env={"JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "needs platform 'tpu'" in proc.stderr


_AOT_SERVE_LAYER = """
import json
import os
os.environ.setdefault("TPU_LOG_DIR", "disabled")
import jax
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
import chip_smoke
# a compile for a described device is written to the persistent cache
# and cannot be read back without the chip
jax.config.update("jax_enable_compilation_cache", False)
try:
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
except Exception as e:
    print("NO_TOPOLOGY " + repr(e))
    raise SystemExit(0)
one_chip = SingleDeviceSharding(topo.devices[0])
out = {}
for qw in (1, 128):
    compiled, pool = chip_smoke._compile_serve_layer(
        qw, sharding=one_chip, **chip_smoke._HD128_LAYER)
    text = compiled.as_text()
    out[qw] = {"pool_copies": chip_smoke._pool_copies(text, pool),
               "kernels": text.count("tpu_custom_call"),
               "temp_bytes": compiled.memory_analysis().temp_size_in_bytes,
               "pool_bytes": pool.size * pool.dtype.itemsize}
print("RESULT " + json.dumps(out))
"""


def test_serve_layer_compiles_for_v5e_with_no_copy_of_a_page_pool():
    """The guard that keeps ``copy f32[8,2049,16,128]`` (a fifth of the
    batch cell's busy time until PR 26) from coming back: one layer of
    the ragged step — ``_scatter_pages`` on both donated pools, then the
    Mosaic kernel — compiled ahead of time for a v5e at the batch cell's
    geometry holds no copy of a pool, and at Q=1 its temporaries are a
    small fraction of one pool.  In a child, because a Mosaic check
    failure aborts the process."""
    proc = _run(["-c", _AOT_SERVE_LAYER], env={"JAX_PLATFORMS": "cpu"})
    lines = proc.stdout.strip().splitlines()
    if lines and lines[-1].startswith("NO_TOPOLOGY"):
        pytest.skip(f"no v5e topology can be described here: {lines[-1]}")
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    out = json.loads(lines[-1].removeprefix("RESULT "))
    for qw in ("1", "128"):
        assert out[qw]["kernels"] >= 1, out
        assert out[qw]["pool_copies"] == 0, out
    assert out["1"]["temp_bytes"] < out["1"]["pool_bytes"] // 8, out


_AOT_KEY192_LAYERS = _AOT_SERVE_LAYER.replace(
    """for qw in (1, 128):
    compiled, pool = chip_smoke._compile_serve_layer(
        qw, sharding=one_chip, **chip_smoke._HD128_LAYER)
""", """for kind, geometry, qw in (
        ("full", chip_smoke._KEY192_FULL_LAYER, 1),
        ("window", chip_smoke._KEY192_WINDOW_LAYER, 1),
        ("window_q128", chip_smoke._KEY192_WINDOW_LAYER, 128),
        ("unpadded", dict(chip_smoke._KEY192_WINDOW_LAYER, hd=192), 1)):
    compiled, pool = chip_smoke._compile_serve_layer(
        qw, sharding=one_chip, **geometry)
    qw = kind
""")


def test_window_and_full_layers_of_wide_keys_compile_with_no_pool_copy():
    """The third configuration's attention: a full layer (4 kv heads,
    4097 pages) and a window layer (8 kv heads, a sink a head, a ring of
    73 pages a lane), keys of 192 in pools padded to 256 and values of
    128, compile for a v5e with no copy of a key pool; a key pool left
    192 wide is re-laid twice, which is why ``generation._pool_width``
    pads it."""
    assert "_KEY192_FULL_LAYER" in _AOT_KEY192_LAYERS
    proc = _run(["-c", _AOT_KEY192_LAYERS], env={"JAX_PLATFORMS": "cpu"})
    lines = proc.stdout.strip().splitlines()
    if lines and lines[-1].startswith("NO_TOPOLOGY"):
        pytest.skip(f"no v5e topology can be described here: {lines[-1]}")
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    out = json.loads(lines[-1].removeprefix("RESULT "))
    for kind in ("full", "window", "window_q128"):
        assert out[kind]["kernels"] == 1, out
        assert out[kind]["pool_copies"] == 0, out
    assert out["unpadded"]["pool_copies"] == 2, out


_AOT_HD64_LAYERS = _AOT_SERVE_LAYER.replace(
    """for qw in (1, 128):
    compiled, pool = chip_smoke._compile_serve_layer(
        qw, sharding=one_chip, **chip_smoke._HD128_LAYER)
""", """for kind, geometry, qw, rows in (
        ("q1", chip_smoke._HD64_LAYER, 1, None),
        ("q1024", chip_smoke._HD64_LAYER, 1024, 1032),
        ("unpadded", dict(chip_smoke._HD64_LAYER, hd=64), 1, None)):
    compiled, pool = chip_smoke._compile_serve_layer(
        qw, sharding=one_chip, rows=rows, **geometry)
    qw = kind
""")


def test_layer_of_heads_of_64_compiles_with_no_pool_copy():
    """The sixth configuration's attention layer (8 lanes, 32 heads over
    8 kv heads of 64, 5121 pages of 16) in pools padded to 128, compiled
    for a v5e: no copy of a pool at Q=1 nor as the Q=1024 step runs it
    (1,032 packed rows).  The same layer left 64 wide re-lays its pools,
    which is why ``generation._pool_width`` pads every head to whole
    lane tiles."""
    assert "_HD64_LAYER" in _AOT_HD64_LAYERS
    proc = _run(["-c", _AOT_HD64_LAYERS], env={"JAX_PLATFORMS": "cpu"})
    lines = proc.stdout.strip().splitlines()
    if lines and lines[-1].startswith("NO_TOPOLOGY"):
        pytest.skip(f"no v5e topology can be described here: {lines[-1]}")
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    out = json.loads(lines[-1].removeprefix("RESULT "))
    for kind in ("q1", "q1024"):
        assert out[kind]["kernels"] == 1, out
        assert out[kind]["pool_copies"] == 0, out
    assert out["unpadded"]["pool_copies"] >= 2, out


_AOT_PACKED_STEP = _AOT_SERVE_LAYER.replace(
    """out = {}
for qw in (1, 128):
    compiled, pool = chip_smoke._compile_serve_layer(
        qw, sharding=one_chip, **chip_smoke._HD128_LAYER)
    text = compiled.as_text()
""", """out = {}
# the chip's routes (the Mosaic kernel, donated pools) for a program
# that is compiled here and run nowhere
jax.default_backend = lambda: "tpu"
for qw in (1024,):
    compiled, pool, rows = chip_smoke._compile_packed_step(
        qw, sharding=one_chip)
    text = compiled.as_text()
    out["rows"] = rows
    out["matmul_rows"] = chip_smoke._matmul_rows(text)
    import re
    out["matmul_shapes"] = sorted(re.findall(
        r"= (\\w+\\[[\\d,]+\\])\\S* convolution\\(", text))
    [call] = [l for l in text.splitlines() if "tpu_custom_call" in l]
    out["attn_operands"] = re.search(
        r"custom-call\\((.*?)\\), custom_call_target", call)[1].count("%")
    out["attn_pool_operands"] = call.split("backend_config")[0].split(
        "operand_layout_constraints=")[1].count(
        "f32[" + ",".join(str(n) for n in pool.shape) + "]")
""")

# the matrix products of serve_step_q1024 for one Mistral-width layer as
# the LLaMA closure compiled them at PR 28 (sandbox AOT for a v5e): k and
# v, gate and up, q, the output projection and down, and the head over
# the 8 last rows
_Q1024_PRODUCTS_AT_PR28 = sorted(
    ["f32[1032,1024]"] * 2 + ["f32[1032,14336]"] * 2
    + ["f32[1032,4096]"] * 3 + ["f32[8,2048]"])


def test_q1024_program_multiplies_packed_rows_and_copies_no_pool():
    """The guard beside PR 26's: the engine's own Q=1024 program
    (``serve_step_q1024``) of one Mistral-width layer, compiled ahead of
    time for a v5e, runs every matrix product over the 1,032 packed rows
    (a chunk of 1,024 and a token a lane) or over the 8 last rows of the
    head — none over 8 lanes x 1,024 = 8,192 — and holds no copy of a
    page pool.  The one body of the ragged step multiplies what the
    LLaMA closure multiplied: the same products, shape for shape."""
    assert "_compile_packed_step" in _AOT_PACKED_STEP
    proc = _run(["-c", _AOT_PACKED_STEP], env={"JAX_PLATFORMS": "cpu"})
    lines = proc.stdout.strip().splitlines()
    if lines and lines[-1].startswith("NO_TOPOLOGY"):
        pytest.skip(f"no v5e topology can be described here: {lines[-1]}")
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    out = json.loads(lines[-1].removeprefix("RESULT "))
    assert out["rows"] == 1032
    assert set(out["matmul_rows"]) == {8, 1032}, out
    assert out["matmul_shapes"] == _Q1024_PRODUCTS_AT_PR28, out
    assert out["1024"]["kernels"] == 1, out
    assert out["1024"]["pool_copies"] == 0, out
    # the attention call walks the pools itself: the key pool and the
    # value pool once each beside q and the four prefetched arrays (the
    # lengths, the tables, the tile list), where a block a page of the
    # causal walk was sixteen pool operands
    assert out["attn_pool_operands"] == 2, out
    assert out["attn_operands"] == 7, out
    # q gathered for the kernel and its output, no [8192, ...] buffers
    # of the feed-forward's width: well under a pool and a half
    assert out["1024"]["temp_bytes"] < 3 * out["1024"]["pool_bytes"], out


_AOT_PACKED_ATTENTION = _AOT_SERVE_LAYER.replace(
    """for qw in (1, 128):
    compiled, pool = chip_smoke._compile_serve_layer(
        qw, sharding=one_chip, **chip_smoke._HD128_LAYER)
    text = compiled.as_text()
""", """import re
for kind, geometry in (("full", chip_smoke._KEY192_FULL_LAYER),
                       ("window", chip_smoke._KEY192_WINDOW_LAYER)):
    compiled, pool = chip_smoke._compile_serve_layer(
        1024, sharding=one_chip, rows=1032, **geometry)
    text = compiled.as_text()
    qw = kind
    [call] = [l for l in text.splitlines() if "tpu_custom_call" in l]
    out[kind + "_kernel_out"] = re.search(r"= f32\\[([\\d,]+)\\]", call)[1]
    out[kind + "_shapes"] = sorted(set(re.findall(
        r"= f32\\[(\\d+(?:,\\d+){2,})\\]", text)))
""")

# the temporaries of the same layer at the parent of PR 34, where q and
# the output were laid out [8 lanes, 1,024] around the launch (sandbox
# AOT for a v5e): 1,074,128,896 B a full layer, 1,074,451,456 a window one
_Q1024_ATTENTION_TEMP_AT_PR33 = {"full": 1074128896, "window": 1074451456}


def test_q1024_attention_layer_holds_no_lanes_by_width_array():
    """The guard beside the one above: one full and one window attention
    layer at the longgen cell's widths (64 heads, keys of 192 in 256,
    values of 128) as the engine's Q=1,024 step runs them — 1,032 packed
    rows of 8 lanes — compiled ahead of time for a v5e.  No array of q,
    of the output or of anything else has 8 x 1,024 rows (dimensions 8
    and 1,024 side by side, or 8,192): the launch's grid is the step's
    41 tile slots, and what is gathered for it is 41 x 32 = 1,312 rows.
    Its temporaries are 87 MB where the parent's were 1,074 MB.

    The kernel's output stays ``f32[slots, heads, block_q, 128]``:
    4-dimensional with a tile's rows THIRD.  The benchmark's readers
    (``benchmark/layer_metrics/experts_window.py``) call a launch narrow
    — a decode-only step's — when that third dimension is at most 8, and
    the expert roofline counts its steps by the same flag."""
    assert "_KEY192_WINDOW_LAYER" in _AOT_PACKED_ATTENTION
    proc = _run(["-c", _AOT_PACKED_ATTENTION], env={"JAX_PLATFORMS": "cpu"})
    lines = proc.stdout.strip().splitlines()
    if lines and lines[-1].startswith("NO_TOPOLOGY"):
        pytest.skip(f"no v5e topology can be described here: {lines[-1]}")
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    out = json.loads(lines[-1].removeprefix("RESULT "))
    for kind in ("full", "window"):
        assert out[kind]["kernels"] == 1 and out[kind]["pool_copies"] == 0
        assert out[kind + "_kernel_out"] == "41,64,32,128", out
        for shape in out[kind + "_shapes"]:
            dims = [int(n) for n in shape.split(",")]
            assert 8192 not in dims, shape
            assert not (8 in dims and 1024 in dims), shape
        assert out[kind]["temp_bytes"] \
            < _Q1024_ATTENTION_TEMP_AT_PR33[kind] // 8, out


_AOT_STATE_LAYER = """
import json
import os
import types
os.environ.setdefault("TPU_LOG_DIR", "disabled")
import jax
import jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
import chip_smoke
from paddle_tpu.models.generation import build_ragged_decode_step
from paddle_tpu.models.solar_open2 import SolarOpen2Config
from paddle_tpu.serving import ServingEngine
jax.config.update("jax_enable_compilation_cache", False)
try:
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
except Exception as e:
    print("NO_TOPOLOGY " + repr(e))
    raise SystemExit(0)
one_chip = SingleDeviceSharding(topo.devices[0])
# one linear-attention layer at the published widths (64 heads of 128,
# hidden 4096) with one held expert of 320 behind a small vocabulary;
# the parameters are shapes, nothing of their size is allocated here
cfg = SolarOpen2Config(vocab_size=2048, num_hidden_layers=1, gqa_layers=[],
                       held_experts=(0, 1), max_position_embeddings=4096)
h, nk, r = 4096, 8192, 128
layer = {"ln1_w": (h,), "wq": (h, nk), "wk": (h, nk), "wv": (h, nk),
         "conv_q": (4, nk), "conv_k": (4, nk), "conv_v": (4, nk),
         "wf_down": (h, r), "wf_up": (r, nk), "dt_bias": (nk,),
         "a_log": (64,), "wbeta": (h, 64), "wgate_down": (h, r),
         "wgate_up": (r, nk), "out_norm_w": (128,), "wo": (nk, h),
         "ln2_w": (h,), "router_w": (h, 320), "router_b": (320,),
         "wg": ((h, 1280),), "wu": ((h, 1280),), "wd": ((1280, h),),
         "shared_wg": (h, 1280), "shared_wu": (h, 1280),
         "shared_wd": (1280, h)}
shapes = {"embed": (2048, h), "norm_w": (h,), "lm_w": (2048, h),
          "layers": [layer]}
is_shape = lambda x: isinstance(x, tuple) and all(
    isinstance(v, int) for v in x)
sds = lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
    shape, dtype, sharding=one_chip)
params = jax.tree.map(sds, shapes, is_leaf=is_shape)
stub = types.SimpleNamespace(config=cfg, described_params=lambda: None)
engine = ServingEngine(
    types.SimpleNamespace(
        config=cfg,
        build_ragged_decode_step=lambda: build_ragged_decode_step(stub)),
    max_batch=8, page_size=16, num_pages=9, max_prefill_chunk=128,
    prefix_caching=False)
state = engine._pools[0][0]
# the chip's routes for a program that is compiled here and run nowhere
jax.default_backend = lambda: "tpu"
out = {"state_shape": list(state.shape)}
for qw in (1, 128):
    args = list(chip_smoke._engine_program_args(engine, qw, one_chip))
    args[0] = params
    args[8] = sds((8, engine.scheduler.ppseq + 1), jnp.int32)   # + slot
    compiled = engine._program(qw).lower(*args).compile()
    text = compiled.as_text()
    ma = compiled.memory_analysis()
    out[qw] = {"state_copies": chip_smoke._pool_copies(text, state),
               "scan_kernels": sum("tpu_custom_call" in l
                                   and "gated_delta_scan" in l
                                   for l in text.splitlines()),
               "solves": text.count("InvertDiagBlocksLowerTriangular"),
               "temp_bytes": ma.temp_size_in_bytes,
               "alias_bytes": ma.alias_size_in_bytes,
               "state_bytes": state.size * 4}
print("RESULT " + json.dumps(out))
"""


def test_state_layer_compiles_for_v5e_and_updates_the_state_in_place():
    """The engine's own programs for one linear-attention layer at the
    published widths (a state of 8 x 64 x 128 x 128 float32, donated),
    compiled ahead of time for a v5e: neither the decode-only program
    (the one-token update) nor a prefill program (the chunked scan, one
    kernel that carries the state in VMEM) holds a layout copy of the
    state, both hand the donated state back in its own buffer, and the
    decode-only program's temporaries stay under one state array."""
    proc = _run(["-c", _AOT_STATE_LAYER], env={"JAX_PLATFORMS": "cpu"})
    lines = proc.stdout.strip().splitlines()
    if lines and lines[-1].startswith("NO_TOPOLOGY"):
        pytest.skip(f"no v5e topology can be described here: {lines[-1]}")
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    out = json.loads(lines[-1].removeprefix("RESULT "))
    assert out["state_shape"] == [8, 64, 128, 128]
    for qw in ("1", "128"):
        assert out[qw]["state_copies"] == 0, out
        # the state and the convolution's tail come back in place
        assert out[qw]["alias_bytes"] >= out[qw]["state_bytes"], out
    # the chunked scan is one kernel in the prefill program, with no
    # triangular solve of XLA's beside it, and the decode-only program
    # holds none
    assert out["1"]["scan_kernels"] == 0 and out["128"]["scan_kernels"] == 1
    assert out["1"]["solves"] == out["128"]["solves"] == 0, out
    assert out["1"]["temp_bytes"] < out["1"]["state_bytes"], out


_AOT_LATENT_LAYER = """
import json
import os
import re
import types
os.environ.setdefault("TPU_LOG_DIR", "disabled")
import jax
import jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
import chip_smoke
from paddle_tpu.models.generation import build_ragged_decode_step
from paddle_tpu.models.glm5 import Glm5Config
from paddle_tpu.serving import ServingEngine
jax.config.update("jax_enable_compilation_cache", False)
try:
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
except Exception as e:
    print("NO_TOPOLOGY " + repr(e))
    raise SystemExit(0)
one_chip = SingleDeviceSharding(topo.devices[0])
# one latent-attention layer at the published widths (hidden 6144, 64
# heads of 192 + 64 over a latent of 512, an index of 32 heads of 128
# that keeps 2,048 keys) with one held expert of 256 behind a small
# vocabulary; the parameters are shapes, nothing of their size is
# allocated here
cfg = Glm5Config(vocab_size=2048, num_hidden_layers=1,
                 first_k_dense_replace=0, held_experts=(0, 1),
                 max_position_embeddings=8192)
h = 6144
layer = {"ln1_w": (h,), "wq_a": (h, 2048), "q_norm_w": (2048,),
         "wq_b": (2048, 16384), "wkv_a": (h, 576), "kv_norm_w": (512,),
         "w_uk": (64, 192, 512), "w_uv": (64, 512, 256), "wo": (16384, h),
         "wi_q": (2048, 4096), "wi_k": (h, 128), "wi_k_norm_w": (128,),
         "wi_k_norm_b": (128,), "wi_w": (h, 32), "ln2_w": (h,),
         "router_w": (h, 256), "router_b": (256,),
         "wg": ((h, 2048),), "wu": ((h, 2048),), "wd": ((2048, h),),
         "shared_wg": (h, 2048), "shared_wu": (h, 2048),
         "shared_wd": (2048, h)}
shapes = {"embed": (2048, h), "norm_w": (h,), "lm_w": (2048, h),
          "rope": {"1e+06": ((8192, 64), (8192, 64))}, "layers": [layer]}
is_shape = lambda x: isinstance(x, tuple) and all(
    isinstance(v, int) for v in x)
sds = lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
    shape, dtype, sharding=one_chip)
params = jax.tree.map(sds, shapes, is_leaf=is_shape)
stub = types.SimpleNamespace(config=cfg, described_params=lambda: None)
engine = ServingEngine(
    types.SimpleNamespace(
        config=cfg,
        build_ragged_decode_step=lambda: build_ragged_decode_step(stub)),
    max_batch=8, page_size=16, num_pages=4097, max_prefill_chunk=1024,
    prefix_caching=False)
pools = engine._pools[0]
# the chip's routes for a program that is compiled here and run nowhere
jax.default_backend = lambda: "tpu"
out = {"pool_shapes": [list(a.shape) for a in pools]}
for qw in (1, 1024):
    args = list(chip_smoke._engine_program_args(engine, qw, one_chip))
    args[0] = params
    compiled = engine._program(qw).lower(*args).compile()
    text = compiled.as_text()
    ma = compiled.memory_analysis()
    calls = [l for l in text.splitlines() if "tpu_custom_call" in l]
    out[qw] = {"pool_copies": [chip_smoke._pool_copies(text, a)
                               for a in pools],
               "kernels": len(calls),
               "expert_kernels": sum("expert_swiglu" in l for l in calls),
               "whiles": len(re.findall(r" while\\(", text)),
               "temp_bytes": ma.temp_size_in_bytes,
               "alias_bytes": ma.alias_size_in_bytes,
               "pool_bytes": sum(a.size * 4 for a in pools)}
print("RESULT " + json.dumps(out))
"""


def test_latent_layer_compiles_for_v5e_and_writes_its_pools_in_place():
    """The engine's own programs for one latent-attention layer at the
    published widths (a latent pool ``[1, 4097, 16, 640]`` and an index
    pool ``[1, 4097, 16, 128]``, donated), compiled ahead of time for a
    v5e: neither the decode-only program (index scores over the lanes'
    gathered index keys, a top-k, 2,048 gathered rows a lane) nor the
    Q=1024 program (blocks of 128 rows under a mask, whose loops the
    decode-only program does not hold) copies a pool, both hand both
    pools back in their own buffers, no Mosaic kernel is involved in the
    mixer (the decode-only program's one is its one held expert's,
    ``ops/pallas/expert_swiglu.py``, since PR 36), and the temporaries — printed — stay under the pools' size at Q=1 and
    under 1 GB at Q=1024: nothing of ``[rows, 64, kv_len]`` or ``[rows,
    2048, 640]`` is whole in memory."""
    proc = _run(["-c", _AOT_LATENT_LAYER], env={"JAX_PLATFORMS": "cpu"})
    lines = proc.stdout.strip().splitlines()
    if lines and lines[-1].startswith("NO_TOPOLOGY"):
        pytest.skip(f"no v5e topology can be described here: {lines[-1]}")
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    out = json.loads(lines[-1].removeprefix("RESULT "))
    print(out)
    assert out["pool_shapes"] == [[1, 4097, 16, 640], [1, 4097, 16, 128]]
    for qw in ("1", "1024"):
        assert out[qw]["pool_copies"] == [0, 0], out
        assert out[qw]["kernels"] == out[qw]["expert_kernels"] \
            == (1 if qw == "1" else 0), out
        # the latent rows and the index keys come back in place
        assert out[qw]["alias_bytes"] >= out[qw]["pool_bytes"], out
    assert out["1"]["whiles"] < out["1024"]["whiles"], out
    assert out["1"]["temp_bytes"] < out["1"]["pool_bytes"], out
    assert out["1024"]["temp_bytes"] < 1e9, out


_REPORT_CACHE_DIR = """
import jax
import paddle_tpu
from paddle_tpu.flags import set_flags
before = jax.config.jax_compilation_cache_dir
set_flags({"FLAGS_tuning_cache_dir": %r})
set_flags({"FLAGS_tuning_cache_dir": ""})
assert jax.config.jax_compilation_cache_dir == before
print(before)
"""


def test_compile_cache_dir_left_to_the_environment_when_set(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: neither the import nor set_flags
    names another directory."""
    proc = _run(["-c", _REPORT_CACHE_DIR % str(tmp_path / "tune")],
                env={"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "xla"),
                     "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip() == str(tmp_path / "xla")


def test_compile_cache_dir_defaults_beside_the_checkout(tmp_path):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    proc = subprocess.run(
        [sys.executable, "-c", _REPORT_CACHE_DIR % str(tmp_path / "tune")],
        capture_output=True, text=True, timeout=600, cwd=str(tmp_path),
        env={**env, "JAX_PLATFORMS": "cpu", "PYTHONPATH": _REPO})
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip() == os.path.join(_REPO, ".jax_cache")


def test_chip_peak_flops_raises_on_an_unknown_kind():
    import jax
    from paddle_tpu.device import chip_peak_flops

    class Dev:
        device_kind = "TPU v5 lite"

    assert chip_peak_flops(Dev()) == 197e12
    Dev.device_kind = "TPU v99"
    with pytest.raises(ValueError, match="TPU v99"):
        chip_peak_flops(Dev())
    with pytest.raises(ValueError, match="no peak FLOPs"):
        chip_peak_flops(jax.devices()[0])        # the CPU test mesh


def test_jax_device_rejects_an_id_past_the_attached_devices():
    import jax
    from paddle_tpu.device import CPUPlace, TPUPlace, jax_device
    assert jax_device(CPUPlace()) == jax.devices("cpu")[0]
    with pytest.raises(ValueError, match="out of range"):
        jax_device(TPUPlace(len(jax.devices())))

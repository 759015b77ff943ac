"""Every operation of a serve step and of the train step runs under a
named part (``models.generation.STEP_PARTS`` / ``TRAIN_STEP_PARTS``).

Counts, never times: the engine's own programs of each family a
benchmark cell serves (at the builders' ``rehearse`` sizes) and the GPT
train step are compiled here on the CPU, and the ``op_name`` of the
compiled HLO — the string a device trace holds as ``tf_op`` — is read
back.  The compile cache is off for these compiles: its key leaves an
operation's metadata out, so a hit would hand back whatever names the
program had when the entry was written.
"""
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness                                   # noqa: E402
from paddle_tpu.models.generation import (STEP_PARTS,           # noqa: E402
                                          TRAIN_STEP_PARTS, _mixer_part)

# the instructions that do a step's work (a fusion carries its root's
# path), as the compiled HLO text writes them
_WORK = re.compile(r" (dot|convolution|custom-call|while|conditional|scatter"
                   r"|gather|dynamic-update-slice|reduce|fusion)\(")
_OP_NAME = re.compile(r'op_name="([^"]+)"')

# family -> the cell whose configuration file describes it
FAMILIES = {"gpt": "gpt3-760m.chat", "llama": "mistral-7b-8l.batch",
            "mimo": "mimo-v2.5-7l-ep32.longgen",
            "solar_open2": "solar-open2-8l-ep32.longdoc",
            "glm5": "glm-5-5l-ep32.longctx"}


_FLAGS = ("serving_engine", "pallas_interpret")


@pytest.fixture(scope="module", autouse=True)
def _no_compile_cache_and_flags_put_back():
    from jax.experimental.compilation_cache import compilation_cache
    from paddle_tpu.flags import get_flag, set_flags
    was = jax.config.jax_enable_compilation_cache
    flags = {"FLAGS_" + f: get_flag(f) for f in _FLAGS}
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    # the Pallas kernels' names are part of what is read back
    set_flags({"FLAGS_" + f: True for f in _FLAGS})
    yield
    set_flags(flags)
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _paths(hlo_text: str):
    """The name components of every working instruction that has a path
    (the first of a merged instruction's paths), wrappers and all."""
    out = []
    for line in hlo_text.splitlines():
        m = _OP_NAME.search(line)
        if m and _WORK.search(line):
            out.append(m[1].split(";", 1)[0].split("/")[1:])
    return out


def _bare(component: str) -> str:
    """``transpose(jvp(attention))`` -> ``attention``."""
    return re.sub(r"^(?:\w+\()*|\)*$", "", component)


_ENGINES = {}


def _programs(family: str):
    """The engine's programs for the family at its rehearsal size, each
    with the abstract arguments the engine called it with: a prompt of
    40 tokens and two new ones reach a wide program and the decode-only
    one."""
    if family in _ENGINES:
        return _ENGINES[family]
    from paddle_tpu.serving import ServingEngine
    cfg = harness.load_cell(FAMILIES[family], rehearse=True)["config"]
    model = harness.builder_for(cfg).build(cfg, 1, training=False)
    s = cfg["serve"]
    engine = ServingEngine(
        model, max_batch=s["max_batch"], page_size=s["page_size"],
        num_pages=s["num_pages"], dtype=s["dtype"],
        max_prefill_chunk=int(s.get("max_prefill_chunk", 0)),
        prefix_caching=bool(s.get("prefix_caching", True)))
    called, program = {}, engine._program

    def spy(qw):
        prog = program(qw)

        def call(*args):
            called.setdefault(qw, jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(jnp.shape(a),
                                               jnp.result_type(a)), args))
            return prog(*args)
        return call
    engine._program = spy
    engine.start()
    try:
        engine.generate(list(range(1, 41)), max_new_tokens=2)
    finally:
        engine.stop(drain=False)
    _ENGINES[family] = (model.config.description(), program, called)
    return _ENGINES[family]


def _expected(md, wide: bool):
    """The parts and the kept names a family's description implies."""
    parts, names = {"embed", "lm_head", "sample"}, set()
    for d in md.layers:
        part = _mixer_part(d)
        parts.add(part)
        if part == "attention":
            names |= {"qkv_proj", "kv_write", "attn_launch", "attn_out",
                      "ragged_paged_attn_window"
                      if d.attention.window is not None
                      else "ragged_paged_attn"}
            if d.attention.gate:
                names.add("attention_gate")
        elif part == "linear_attention":
            names.add("linear_attn_step")
            if wide:
                names.add("linear_attn_scan")
        else:
            names |= {"kv_write", "sparse_attention"}
            if d.latent_attention.index is not None:
                names |= {"index_select", "index_score", "index_topk"}
        ff = d.feed_forward
        if ff.held is None:
            parts.add("feed_forward")
        else:
            parts.add("experts")
            names |= {"router", "expert_matmul"}
            if ff.shared_width:
                names.add("shared_expert")
    return parts, names


@pytest.mark.parametrize("wide", [False, True], ids=["q1", "wide"])
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_every_operation_of_a_serve_program_sits_under_one_part(family,
                                                                wide):
    md, program, called = _programs(family)
    assert 1 in called and len(called) > 1, sorted(called)
    qw = max(called) if wide else 1
    text = program(qw).lower(*called[qw]).compile().as_text()
    assert f"jit(serve_step_q{qw})/" in text
    paths = _paths(text)
    assert len(paths) > 20
    seen_parts, seen_names = set(), set()
    for comps in paths:
        found = {c for c in comps if c in STEP_PARTS}
        assert len(found) == 1, "/".join(comps)
        seen_parts |= found
        seen_names.update(comps)
    parts, names = _expected(md, wide)
    assert seen_parts == parts
    assert names <= seen_names, names - seen_names
    # a sub-scope stands under its own part and no other
    for comps in paths:
        if "attn_launch" in comps or "qkv_proj" in comps \
                or "attn_out" in comps:
            assert "attention" in comps, comps
        if "router" in comps or "expert_matmul" in comps:
            assert "experts" in comps, comps
        if "kv_write" in comps:
            assert {"attention", "latent_attention"} & set(comps), comps


def _train_step_text():
    from benchmark.runners import train
    cfg = harness.load_cell("gpt3-760m.pretrain", rehearse=True)["config"]
    model = harness.builder_for(cfg).build(cfg, 1, training=True)
    step = train._train_step(model, cfg["train"])
    ids = np.random.RandomState(0).randint(
        0, cfg["vocab_size"], (2, 64)).astype("int64")
    step(ids, ids)                     # creates the optimizer's state
    step(ids, ids)                     # the steady program
    return step._jitted.lower(*step._cost_args).compile().as_text()


def test_every_operation_of_the_train_step_sits_under_one_part():
    """AMP O2, recompute on, as the pretrain cell trains: the first
    forward runs plainly under its part, the recomputed one and the
    backward under ``backward/<part>`` (the tape re-opens a node's scopes
    around its vjp), the update under ``optimizer`` with the master
    weights' cast under ``cast_params``; what the tape adds itself sits
    under ``backward`` alone."""
    text = _train_step_text()
    assert "jit(train_step)/" in text
    paths = _paths(text)
    model_parts = set(TRAIN_STEP_PARTS) - {"backward"}
    seen = set()
    for comps in paths:
        bare = [_bare(c) for c in comps]
        found = {c for c in bare if c in model_parts}
        if found == {"optimizer", "cast_params"}:
            found = {"optimizer"}
        assert len(found) == 1 or (not found and "backward" in bare), \
            "/".join(comps)
        seen |= found
    assert seen == model_parts - {"cast_params"}
    joined = ["/".join(c) for c in paths]

    def some(*needles):
        return any(all(n in j for n in needles) for j in joined)
    assert some("backward/attention/", "transpose(")          # backward
    assert some("backward/mlp/", "transpose(")
    assert some("backward/attention/jvp(")                    # recompute
    assert some("backward/mlp/jvp(")
    assert some("backward/lm_head/", "transpose(")
    assert any(j.startswith("attention/") for j in joined)    # forward
    assert any(j.startswith("optimizer/adamw/") for j in joined)
    # the kept kernel names, wrapped or not
    every = {_bare(c) for comps in paths for c in comps}
    assert {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv", "ln_fwd",
            "ln_bwd", "adamw"} <= every
    # the cast is elementwise and fuses into its consumer, so it is
    # looked for among all instructions
    assert "jit(train_step)/optimizer/cast_params/" in text

"""What a compiled serve step moves into VMEM ahead of its branches.

XLA's memory-space assignment on a TPU treats the weights a
``conditional``'s branch multiplies as operands of the ``conditional``,
and where they fit it fetches them into VMEM (``S(1)`` in a layout)
BEFORE the ``conditional``, whether the branch is then taken or not
(PERF.md section 6, PR 36).  Those fetches are the ``copy-start`` and
``slice-start`` operations of the optimised HLO's ENTRY computation
whose result lives in ``S(1)``.  This tool compiles a cell's described
step for a described v5e — no chip — and prints, by the shape of the
array fetched from, how many bytes ENTRY moves that way:

    python tools/aot_prefetch_tally.py --workload solar-open2-8l-ep32.longdoc

The parameters are shapes (nothing of a model's size is allocated but
the engine's page pools, on the host), nothing runs, and no number here
is a time.
"""
from __future__ import annotations

import argparse
import os
import re
import sys
import types
from typing import Dict, List, Tuple

_ITEMSIZE = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
             "u8": 1, "pred": 1, "s64": 8, "f64": 8}
_SHAPE = re.compile(r"(\w+)\[([\d,]*)\]\{([^}]*)\}")
_START = re.compile(
    r"^\s*%?[\w.\-]+ = \((.*)\) (?:copy|slice)-start\(%?([\w.\-]+)")
# a held expert's matrix among the step's parameters, as jax names a
# leaf of the tree (params["layers"][3]["wg"][7] is ...layers___3___wg___7_)
# with its numbers written "#"
_HELD = re.compile(r"__w[gud]___#_")


def _nbytes(dtype: str, dims: str) -> int:
    n = _ITEMSIZE[dtype]
    for d in dims.split(","):
        n *= int(d) if d else 1
    return n


def entry_prefetches(hlo_text: str) -> Dict[Tuple[str, str], List[int]]:
    """``{(source, fetched shape): [operations, bytes]}`` of the ENTRY
    computation's ``copy-start`` / ``slice-start`` operations whose
    result is in ``S(1)``.  A ``copy-start``'s result is ``(fetched,
    source, context)`` and a ``slice-start``'s ``((source), fetched,
    context)``: the fetched array is the one with ``S(1)`` in its
    layout.  ``source`` is the operand's name with every number in it
    written ``#`` (all layers' and experts' matrices of a kind are one
    row) before the shape of the array fetched from."""
    out: Dict[Tuple[str, str], List[int]] = {}
    in_entry = False
    for line in hlo_text.splitlines():
        if line.startswith("ENTRY "):
            in_entry = True
            continue
        if in_entry and line.startswith("}"):
            break
        m = _START.match(line) if in_entry else None
        if m is None:
            continue
        arrays = [(f"{d}[{dims}]", _nbytes(d, dims), "S(1)" in layout)
                  for d, dims, layout in _SHAPE.findall(m[1]) if dims]
        fetched = [a for a in arrays if a[2]]
        source = [a for a in arrays if not a[2]]
        if not fetched or not source:
            continue
        name = re.sub(r"\d+", "#", m[2]).replace(".#", "")
        acc = out.setdefault((f"{name} {source[0][0]}", fetched[0][0]),
                             [0, 0])
        acc[0] += 1
        acc[1] += fetched[0][1]
    return out


def held_expert_bytes(tally: Dict[Tuple[str, str], List[int]]) -> int:
    """The bytes of the tally whose source is a held expert's matrix."""
    return sum(n_bytes for (source, _), (_, n_bytes) in tally.items()
               if _HELD.search(source))


def report(tally: Dict[Tuple[str, str], List[int]]) -> List[str]:
    """The tally's lines, the largest first, and its totals."""
    lines = [f"{n_bytes / 1e6:10.1f} MB in {n:3d} operations: {fetched} "
             f"of {source}"
             for (source, fetched), (n, n_bytes) in sorted(
                 tally.items(), key=lambda kv: -kv[1][1])]
    lines.append(f"{sum(v[1] for v in tally.values()) / 1e6:10.1f} MB in "
                 f"all, fetched into VMEM in ENTRY")
    lines.append(f"{held_expert_bytes(tally) / 1e6:10.1f} MB of them from "
                 f"held experts' matrices (wg, wu, wd of an expert layer)")
    return lines


def compile_step(workload: str, q_width: int = 1):
    """``(optimised HLO text, bytes of all held experts' matrices)`` of
    the engine's ``serve_step_q<q_width>`` for the cell's
    configuration at its real widths, compiled for one chip of a
    described v5e.  Raises ``RuntimeError`` where no such topology can
    be described (no ``libtpu``)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    import chip_smoke
    from benchmark import harness
    from paddle_tpu.models.generation import build_ragged_decode_step
    from paddle_tpu.serving import ServingEngine
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:1x1",
            chips_per_host_bounds=(1, 1, 1), num_slices=1)
    except Exception as e:
        raise RuntimeError(f"no v5e topology can be described here: {e!r}")
    # a compile for a described device cannot be read back from the cache
    jax.config.update("jax_enable_compilation_cache", False)
    one_chip = SingleDeviceSharding(topo.devices[0])
    cfg = harness.load_cell(workload)["config"]
    model_config = harness.builder_for(cfg)._model_config(cfg)
    [model_class] = [v for k, v in vars(
        sys.modules[type(model_config).__module__]).items()
        if k.endswith("ForCausalLM")]
    # the weights as shapes: the constructor traced, nothing drawn
    shapes = jax.eval_shape(
        lambda: model_class(model_config).described_params())
    params = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), shapes)
    stub = types.SimpleNamespace(config=model_config,
                                 described_params=lambda: None)
    s = cfg["serve"]
    engine = ServingEngine(
        types.SimpleNamespace(
            config=model_config,
            build_ragged_decode_step=lambda: build_ragged_decode_step(stub)),
        max_batch=s["max_batch"], page_size=s["page_size"],
        num_pages=s["num_pages"], dtype=s["dtype"],
        max_prefill_chunk=int(s.get("max_prefill_chunk", 0)),
        prefix_caching=False)
    # the chip's routes (the Mosaic kernels, donated pools) for a program
    # that is compiled here and run nowhere
    jax.default_backend = lambda: "tpu"
    args = list(chip_smoke._engine_program_args(engine, q_width, one_chip))
    args[0] = params
    # the tables as the engine's plans hold them: a window layer's ring
    # and a state layer's slot behind the full layers' page ids
    b, ppseq = args[8].shape
    width = engine._cache.tables(np.zeros((b, ppseq)), np.zeros((b,)),
                                 engine._ring_pages).shape[1]
    args[8] = jax.ShapeDtypeStruct((b, width), args[8].dtype,
                                   sharding=one_chip)
    text = engine._program(q_width).lower(*args).compile().as_text()
    held = [a for lp in shapes["layers"] for name in ("wg", "wu", "wd")
            if isinstance(lp.get(name), tuple) for a in lp[name]]
    return text, sum(a.size * a.dtype.itemsize for a in held)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True,
                    help="a cell of BENCHMARK.json whose configuration's "
                    "step is compiled")
    ap.add_argument("--q-width", type=int, default=1,
                    help="the step program's chunk width (1: decode-only)")
    args = ap.parse_args(argv)
    try:
        text, held = compile_step(args.workload, args.q_width)
    except RuntimeError as e:
        print(e, file=sys.stderr)
        return 3
    for line in report(entry_prefetches(text)):
        print(line)
    print(f"{held / 1e6:10.1f} MB of held experts' matrices in the step, "
          f"in {text.count(' conditional(')} conditionals (sandbox AOT: "
          f"counts of a compile, no time)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

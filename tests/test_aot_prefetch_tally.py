"""``tools/aot_prefetch_tally.py``: what a compiled serve step fetches
into VMEM ahead of its branches.  The parser on a canned ENTRY needs no
compiler; the compile of a whole step needs ``libtpu`` and is skipped
where a v5e cannot be described."""
import importlib.util
import os
import subprocess
import sys

import pytest

_TOOL = os.path.join(os.path.dirname(__file__), os.pardir, "tools",
                     "aot_prefetch_tally.py")
_spec = importlib.util.spec_from_file_location("aot_prefetch_tally", _TOOL)
tally = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(tally)

# a dozen lines of an optimised HLO as the v5e's compiler prints it: a
# branch's computation (not ENTRY: its copy does not count), then ENTRY
# with a cross-program prefetch of a router, a held expert's gate matrix
# copied whole, its down matrix in two slices, the shared expert's gate
# matrix in one slice, a copy that stays in HBM and a done
_CANNED = """\
HloModule jit_serve_step_q1, is_scheduled=true

%region_1.2 (arg_tuple.1: (f32[8,4096], f32[4096,1280])) -> (f32[8,4096]) {
  %copy-start.9 = (f32[4096,1280]{1,0:T(8,128)S(1)}, f32[4096,1280]{1,0:T(8,128)}, u32[]{:S(2)}) copy-start(%get-tuple-element.3)
}

ENTRY %main.120 (params__layers___0___wg___1_.1: f32[4096,1280]) -> f32[8,4096] {
  %copy-start = (f32[4096,320]{0,1:T(8,128)S(1)}, f32[4096,320]{0,1:T(8,128)}, u32[]{:S(2)}) copy-start(%params__layers___0___router_w__.1), cross_program_prefetch_index=0
  %copy-start.57 = (f32[4096,1280]{1,0:T(8,128)S(1)}, f32[4096,1280]{1,0:T(8,128)}, u32[]{:S(2)}) copy-start(%params__layers___0___wg___1_.1)
  %copy-done.57 = f32[4096,1280]{1,0:T(8,128)S(1)} copy-done(%copy-start.57)
  %slice-start.4 = ((f32[1280,4096]{1,0:T(8,128)}), f32[320,4096]{1,0:T(8,128)S(1)}, s32[]{:S(2)}) slice-start(%params__layers___3___wd___7_.1), slice={[0:320], [0:4096]}
  %slice-start.5 = ((f32[1280,4096]{1,0:T(8,128)}), f32[320,4096]{1,0:T(8,128)S(1)}, s32[]{:S(2)}) slice-start(%params__layers___3___wd___7_.1), slice={[320:640], [0:4096]}
  %slice-start.6 = ((f32[4096,1280]{1,0:T(8,128)}), f32[1024,1280]{1,0:T(8,128)S(1)}, s32[]{:S(2)}) slice-start(%params__layers___3___shared_wg__.1), slice={[0:1024], [0:1280]}
  %copy-start.60 = (f32[8,4096]{1,0:T(8,128)}, f32[8,4096]{1,0:T(8,128)}, u32[]{:S(2)}) copy-start(%fusion.12)
  %conditional.3 = (f32[8,4096]{1,0:T(8,128)}) conditional(%bitcast.1, %tuple.1, %tuple.2), branch_computations={%region_0.1, %region_1.2}
}
"""


def test_the_parser_counts_entrys_fetches_into_vmem_by_source():
    got = tally.entry_prefetches(_CANNED)
    gate = 4096 * 1280 * 4
    assert got == {
        ("params__layers___#___router_w__ f32[4096,320]",
         "f32[4096,320]"): [1, 4096 * 320 * 4],
        ("params__layers___#___wg___#_ f32[4096,1280]",
         "f32[4096,1280]"): [1, gate],
        ("params__layers___#___wd___#_ f32[1280,4096]",
         "f32[320,4096]"): [2, 2 * 320 * 4096 * 4],
        ("params__layers___#___shared_wg__ f32[4096,1280]",
         "f32[1024,1280]"): [1, 1024 * 1280 * 4]}
    # a held expert's matrices are told from the shared expert's, whose
    # shapes are the same, by their names
    assert tally.held_expert_bytes(got) == gate + 2 * 320 * 4096 * 4
    lines = tally.report(got)
    assert lines[0].split(":")[0].strip() == "21.0 MB in   1 operations"
    assert "31.5 MB of them from held experts' matrices" in lines[-1]


def test_a_computation_with_no_entry_or_no_fetch_tallies_nothing():
    assert tally.entry_prefetches("") == {}
    assert tally.entry_prefetches(_CANNED.split("ENTRY")[0]) == {}
    assert tally.held_expert_bytes({}) == 0


def test_the_solar_decode_step_fetches_no_held_experts_matrix_ahead():
    """The claim of PR 36 as a compile: ``serve_step_q1`` of the Solar
    Open 2 cell at its real widths, for a described v5e, copies none of
    its 240 held experts' matrices into VMEM in ENTRY (the parent of
    PR 36 copied 3.8 GB of their 5.0 GB), and still holds a
    ``conditional`` a held expert.  In a process of its own: the tool
    steers ``jax.default_backend`` and loads the TPU's compiler."""
    proc = subprocess.run(
        [sys.executable, _TOOL, "--workload", "solar-open2-8l-ep32.longdoc"],
        capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    if proc.returncode == 3 and "no v5e topology" in proc.stderr:
        pytest.skip(proc.stderr.strip().splitlines()[-1])
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    ahead, held = (float(line.split()[0]) for line in lines[-2:])
    assert "held experts' matrices (wg, wu, wd" in lines[-2]
    assert held == round(8 * 10 * 3 * 4096 * 1280 * 4 / 1e6, 1)
    assert ahead < 400.0
    assert "in 80 conditionals" in lines[-1]

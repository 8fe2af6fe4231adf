"""``obs.op_scopes()``: each instruction of the compiled step to the
innermost ``lgbm/`` scope it runs under (``lightgbm_tpu/obs/scopes.py``),
the join a reader of a device trace makes by operation name.

The Mosaic kernels' own names on a described v5e are held in
``tests/test_tpu_compile.py`` (the CPU's route runs their XLA twins)."""
import re

import numpy as np
import pytest

import jax

from lightgbm_tpu.obs import registry as obs
from lightgbm_tpu.obs import scopes
from lightgbm_tpu.ops import step_cache

# instructions that run as operations of their own and are the compiler's
# or a kernel's rather than an elementwise op's
RUN = ("fusion", "custom-call", "copy", "copy-start", "copy-done")
_LINE = re.compile(r"^\s+(?:ROOT\s+)?%?([^\s=]+)\s+=\s+(.*)$")
_OPCODE = re.compile(r"(?:^|\s)([a-z][a-z0-9\-]*)\(")


def opcodes(text):
    """{instruction name: opcode} of an HLO module's text."""
    out = {}
    for line in text.splitlines():
        m = _LINE.match(line)
        if m:
            op = _OPCODE.search(m.group(2))
            out[m.group(1)] = op.group(1) if op else ""
    return out


@pytest.mark.parametrize("op_name, scope", [
    ("jit(step)/jit(grow)/lgbm/wave/loop/while/body/lgbm/wave/hist/"
     "jit(fused_partition_histogram_pallas)/transpose", "lgbm/wave/hist"),
    ("jit(step)/jit(grow)/lgbm/wave/loop/while/body/lgbm/wave/hist/"
     "lgbm/wave/hist_psum/all-reduce", "lgbm/wave/hist_psum"),
    ("jit(step)/jit(grow)/lgbm/root_hist/lgbm/root_hist/psum/psum",
     "lgbm/root_hist/psum"),
    ("jit(step)/lgbm/gradients/rank_pairs/mul", "lgbm/gradients/rank_pairs"),
    ("jit(step)/jit(grow)/lgbm/wave/loop/while/body/lgbm/wave/split_find/"
     "vmap(jit(_where))/select_n", "lgbm/wave/split_find"),
    ("jit(step)/jit(grow)/lgbm/wave/loop/while", "lgbm/wave/loop"),
    ("jit(step)/jit(grow)/mul", None),
    ("jit(step)/jit(grow)", None),
    ("bins", None),
])
def test_scope_is_the_innermost_lgbm_path(op_name, scope):
    assert scopes.scope_of(op_name) == scope


def test_scopes_nest_by_whole_path_components():
    """``hist_psum`` is not ``hist``; ``rank_pairs`` counts as
    ``gradients``; a scope is under itself."""
    assert not scopes.under("lgbm/wave/hist_psum", "lgbm/wave/hist")
    assert scopes.under("lgbm/wave/hist", "lgbm/wave/hist")
    assert scopes.under("lgbm/gradients/rank_pairs", "lgbm/gradients")
    assert scopes.under("lgbm/root_hist/psum", "lgbm/root_hist")
    assert not scopes.under(None, "lgbm/gradients")


HLO = """HloModule jit_step, is_scheduled=true

%fused_computation.1 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %mul.1 = f32[8]{0} multiply(%param_0, %param_0), metadata={op_name="jit(step)/lgbm/wave/bookkeep/mul"}
}

%body.2 (arg: (s32[], f32[8])) -> (s32[], f32[8]) {
  %arg = (s32[], f32[8]{0}) parameter(0)
  %get-tuple-element.3 = f32[8]{0} get-tuple-element(%arg), index=1
  %copy.4 = f32[8]{0} copy(%get-tuple-element.3)
  %fusion.5 = f32[8]{0} fusion(%copy.4), kind=kLoop, calls=%fused_computation.1
  %get-tuple-element.6 = s32[] get-tuple-element(%arg), index=0
  ROOT %tuple.7 = (s32[], f32[8]{0}) tuple(%get-tuple-element.6, %fusion.5)
}

%cond.8 (arg.1: (s32[], f32[8])) -> pred[] {
  %arg.1 = (s32[], f32[8]{0}) parameter(0)
  ROOT %constant.9 = pred[] constant(false)
}

ENTRY %main.10 (bins: f32[8], g: f32[8]) -> f32[8] {
  %bins = f32[8]{0} parameter(0), metadata={op_name="bins"}
  %g = f32[8]{0} parameter(1), metadata={op_name="g"}
  %copy.11 = f32[8]{0} copy(%bins)
  %add.12 = f32[8]{0} add(%copy.11, %g), metadata={op_name="jit(step)/lgbm/gradients/add"}
  %copy-start.13 = (f32[8]{0}, f32[8]{0}, u32[]) copy-start(%add.12)
  %copy-done.14 = f32[8]{0} copy-done(%copy-start.13)
  %constant.15 = s32[] constant(0)
  %tuple.16 = (s32[], f32[8]{0}) tuple(%constant.15, %copy-done.14)
  %while.17 = (s32[], f32[8]{0}) while(%tuple.16), condition=%cond.8, body=%body.2, metadata={op_name="jit(step)/lgbm/wave/loop/while"}
  %sub.18 = f32[8]{0} subtract(%g, %g), metadata={op_name="jit(step)/sub"}
  ROOT %get-tuple-element.19 = f32[8]{0} get-tuple-element(%while.17), index=1
}
"""


def test_what_the_compiler_made_takes_a_scope_from_around_it():
    """A fusion named by the compiler takes its fused computation's scope;
    an async copy its operand's; a copy of an argument or of the loop's
    carry its user's; what has neither the loop's; program code outside
    every scope stays None; a fused computation's instructions are not
    operations, a loop condition's are."""
    table = scopes.parse_hlo(HLO)
    assert table["fusion.5"] == "lgbm/wave/bookkeep"
    assert table["copy-start.13"] == table["copy-done.14"] == "lgbm/gradients"
    assert table["copy.11"] == "lgbm/gradients"
    assert table["copy.4"] == "lgbm/wave/bookkeep"
    assert table["while.17"] == table["get-tuple-element.19"] \
        == table["constant.9"] == "lgbm/wave/loop"
    assert table["sub.18"] is None
    assert "mul.1" not in table


def _booster(objective):
    from conftest import fit_gbdt
    rng = np.random.default_rng(4)
    n = 1024
    X = rng.normal(size=(n, 6))
    params = {"objective": objective, "num_leaves": 7, "max_bin": 31}
    if objective == "lambdarank":
        y = rng.integers(0, 4, n).astype(np.float32)
        return fit_gbdt(X, y, params, num_round=2,
                        group=np.asarray([3, 9, 20, 32] * 16))
    y = (X[:, 0] + X[:, 1] > 0).astype(np.float32)
    return fit_gbdt(X, y, params, num_round=2)


@pytest.mark.parametrize("objective", ["binary", "lambdarank"])
def test_the_steps_table_scopes_every_operation_the_compiler_made(
        objective):
    """The step ``ops/step_cache.build_train_step`` built for a booster,
    as ``obs.op_scopes()`` publishes it: every fusion, custom call and
    copy it runs lies under an ``lgbm/`` scope, and so does every other
    operation of the step but the compiler's own constants; the pair
    gradient's under ``lgbm/gradients``."""
    step_cache.clear()
    scopes.clear()
    g = _booster(objective)
    table = scopes.op_scopes()
    text = g.lower_step().compile().as_text()
    ops = opcodes(text)
    step = scopes.parse_hlo(text)
    assert step and all(table[n] == s for n, s in step.items())
    ran = [n for n in step if ops[n] in RUN]
    assert ran and all(scopes.under(step[n], "lgbm") for n in ran), \
        [(n, step[n]) for n in ran if step[n] is None]
    lost = [n for n, s in step.items() if s is None
            and ops[n] not in ("parameter", "constant")]
    assert not lost, lost
    seen = set(step.values())
    for scope in ("lgbm/gradients", "lgbm/root_hist", "lgbm/wave/hist",
                  "lgbm/wave/split_find", "lgbm/wave/bookkeep",
                  "lgbm/score_update"):
        assert scope in seen, (scope, sorted(map(str, seen)))
    assert ("lgbm/gradients/rank_pairs" in seen) == (objective
                                                     == "lambdarank")
    # the stop check's download is watched too, under a scope of its own
    assert "lgbm/stop_check" in set(table.values())


def test_the_table_is_built_on_the_first_ask_alone():
    """Training watches the step and the stop check (their abstract
    signatures, no buffer) and lowers nothing for the table: its span
    counts a build only when asked, and a second ask builds nothing."""
    step_cache.clear()
    scopes.clear()
    builds = obs.timer(scopes.BUILD_SPAN).count
    g = _booster("binary")
    for _ in range(3):
        g.train_one_iter()
    assert obs.timer(scopes.BUILD_SPAN).count == builds
    with scopes._lock:
        watched = list(scopes._programs)
        assert not scopes._parsed
    assert {p[1] for p in watched} == {scopes.STEP_LABEL, "stop_check"}
    for *_, sig in watched:
        assert not any(isinstance(x, jax.Array)
                       for x in jax.tree_util.tree_leaves(sig))
    assert scopes.op_scopes()
    assert obs.timer(scopes.BUILD_SPAN).count == builds + 1
    scopes.op_scopes()
    assert obs.timer(scopes.BUILD_SPAN).count == builds + 1

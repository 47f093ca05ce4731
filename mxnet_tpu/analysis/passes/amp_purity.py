"""amp-purity pass: mixed precision must stay pure end to end.

The two checks of PR 4's AMP purity lint, on the pass framework
(``python tools/mxlint.py --passes amp-purity``):

1. **jaxpr — no fp32 master feeds a low-precision dot.** Walks the real
   ``TrainStep(amp='bfloat16')`` program (shared ``ProgramIndex`` build)
   recursing into pjit/scan/cond/remat sub-jaxprs; any ``dot_general``
   mixing float32 with bfloat16/float16 operands means a master weight
   (or an un-downcast activation) reached an MXU op without its cast.
   Also asserts the program DOES contain low-precision dots at all — an
   all-f32 "amp" program means the cast pass silently stopped engaging.
2. **AST — no host sync in the overflow-skip path.** The fp16
   loss-scaling contract is that overflow steps cost no host round trip:
   walks ``TrainStep._build``'s traced closures and flags blocking calls
   (the no-sync rule set).
"""

from __future__ import annotations

import ast
import os

from ..core import AnalysisPass, REPO_ROOT, register
from .no_sync import STEP_PY, blocking_calls_in
from .. import jaxpr_driver as _jd


def check_step_purity(step=None, jaxpr=None):
    """Violation messages for the jaxpr check; builds the tiny step if
    neither a step nor a pre-lowered jaxpr is given."""
    import jax

    if jaxpr is None:
        if step is None:
            step = _jd.build_train_step()
        jaxpr = jax.make_jaxpr(step._step_fn)(*step._last_avals)
    mixed = [f"dot_general with operands {dts} — fp32 feeds a "
             f"low-precision dot without a cast" for _, dts in
             _jd.find_mixed_dots(jaxpr)]
    if _jd.count_low_precision_dots(jaxpr) == 0:
        mixed.append(
            "amp step program contains NO low-precision dot_general at "
            "all — the cast pass is not engaging")
    return mixed


def find_overflow_sync_violations(path=None):
    """Blocking host calls inside the TRACED closures of
    ``TrainStep._build`` (``step_core``/``forward_loss``/... — the step
    body XLA compiles, including the fp16 overflow-skip path).
    ``_build``'s own top-level statements run once on host at build time
    and may legitimately coerce hyperparameters."""
    if path is None:
        path = os.path.join(REPO_ROOT, STEP_PY)
    elif not os.path.isabs(path):
        path = os.path.join(REPO_ROOT, path)
    with open(path) as f:
        tree = ast.parse(f.read(), filename=path)
    classes = [n for n in tree.body
               if isinstance(n, ast.ClassDef) and n.name == "TrainStep"]
    if not classes:
        return [(0, f"TrainStep class not found in {path}")]
    builds = [n for n in classes[0].body
              if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
              and n.name == "_build"]
    if not builds:
        return [(classes[0].lineno, "_build method not found — update "
                 "the amp-purity pass if the builder was renamed")]
    out = []
    for fn in ast.walk(builds[0]):
        if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)) and \
                fn is not builds[0]:
            for lineno, msg in blocking_calls_in(fn, "_build"):
                out.append((lineno, msg.replace(
                    "blocks on the device value",
                    "would sync the overflow-skip path")))
    return sorted(set(out))


@register
class AmpPurityPass(AnalysisPass):
    name = "amp-purity"
    ir = "jaxpr"
    description = ("no fp32 master feeds a low-precision dot; the "
                   "overflow-skip path is sync-free")

    def run(self, ctx):
        findings = []
        for lineno, msg in find_overflow_sync_violations():
            findings.append(self.finding(
                "overflow-sync", STEP_PY, lineno, key=msg[:80],
                message=msg))
        for i, msg in enumerate(check_step_purity(
                jaxpr=ctx.programs.train_jaxpr)):
            findings.append(self.finding(
                "mixed-dot", STEP_PY, 0, key=f"jaxpr:{msg[:60]}",
                message="amp jaxpr: " + msg))
        return findings

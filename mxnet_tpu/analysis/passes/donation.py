"""donation pass: every big carried buffer is donated, every donation is
real, and host code never touches a buffer it gave away.

Donation is the memory contract the whole training/serving design leans
on (one extra copy of params+moments+KV pools is exactly the HBM the
batch planner thinks it has), and XLA fails soft when it breaks: an
undonated carry silently doubles peak memory; a donated-but-unaliasable
buffer is a warning in a log nobody reads; host code reading a donated
array dies later with a cryptic "buffer was deleted" — or worse, reads a
stale copy on backends that snapshot. Three rules:

- **contract** (AST) — every ``jax.jit(..., donate_argnums=...)`` in
  ``step.py``/``infer.py`` donates exactly the carried-state parameters
  by NAME: ``train_vals``/``opt_state``/``key``/``t`` (+
  ``scaler_state`` when the variant carries it) for the train step,
  ``state`` (the KV cache / paged pools) for the decode programs — and
  never donates non-carried inputs (``batch``/``label``/
  ``frozen_vals``/``src``). A conditional ``donate = () if c else
  (1,)`` resolves to the non-empty branch; since PR 22 the programs
  donate unconditionally, so the CPU suite runs what the chip runs.
- **aliasable** (jaxpr) — on the REAL lowered programs: each donated
  leaf is consumed by the program, and (for programs that return their
  carry) its aval appears among the outputs so XLA can actually alias
  it. A donated-but-unaliasable buffer is a silent no-op donation.
- **use-after-donate** (AST dataflow) — in the serving scheduler
  (``serving/batcher.py``), an argument passed into a donating engine
  call (``decode_iter``/``prefill_paged`` donate their ``state``) must
  be rebound from the call's result and never read again beforehand.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Tuple

from ..core import AnalysisPass, register
from .. import ast_driver as _ad

STEP_PY = "mxnet_tpu/parallel/step.py"
INFER_PY = "mxnet_tpu/parallel/infer.py"
BATCHER_PY = "mxnet_tpu/serving/batcher.py"

# parameter names that ARE the carried state (must be donated)...
REQUIRED_STEP = {"train_vals", "opt_state", "key", "t", "scaler_state"}
REQUIRED_INFER = {"state"}
# ...and names that must NOT be (inputs read elsewhere / shared params)
FORBIDDEN = {"batch", "label", "frozen_vals", "src", "vl", "values",
             "page_tables", "tokens", "lengths", "active", "prime"}

# serving-side donating calls: callee attr -> donated positional index
DONATING_CALLS = {"decode_iter": 0, "prefill_paged": 0,
                  "prefill_suffix_paged": 0, "spec_draft": 0,
                  "spec_verify": 0}


def _literal_tuple(node) -> Optional[Tuple[int, ...]]:
    if isinstance(node, ast.Tuple) and all(
            isinstance(e, ast.Constant) and isinstance(e.value, int)
            for e in node.elts):
        return tuple(e.value for e in node.elts)
    return None


def _resolve_donate_expr(expr, fn) -> Optional[Tuple[int, ...]]:
    """Resolve a ``donate_argnums`` value: literal tuple, conditional
    ``X if c else Y`` (non-empty branch wins — the donation contract is
    about the real backend), or a Name assigned one of those in ``fn``."""
    lit = _literal_tuple(expr)
    if lit is not None:
        return lit
    if isinstance(expr, ast.IfExp):
        a = _resolve_donate_expr(expr.body, fn)
        b = _resolve_donate_expr(expr.orelse, fn)
        return a if a else b
    if isinstance(expr, ast.Name):
        best = None
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign) and any(
                    isinstance(t, ast.Name) and t.id == expr.id
                    for t in node.targets):
                got = _resolve_donate_expr(node.value, fn)
                if got:
                    best = got
        return best
    return None


def jit_donation_sites(module: _ad.Module) -> List[dict]:
    """Every ``jax.jit(F, donate_argnums=...)`` in the module with the
    donated PARAMETER NAMES resolved: [{fn, lineno, donated,
    candidates}] where candidates is a list of possible parameter-name
    lists (same-named defs in one builder — e.g. the grad-accum
    variants — cannot be disambiguated statically, so the contract
    check accepts a site if ANY candidate satisfies it)."""
    out = []
    # enclosing (outermost) function for each call, for Name resolution
    enclosing: Dict[int, ast.FunctionDef] = {}
    top_fns = []
    for fn in ast.walk(module.tree):
        if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            top_fns.append(fn)
            for node in ast.walk(fn):
                if isinstance(node, ast.Call):
                    enclosing.setdefault(id(node), fn)
    for node in ast.walk(module.tree):
        if not isinstance(node, ast.Call) or \
                _ad.dotted(node.func) != "jax.jit":
            continue
        target = node.args[0] if node.args else None
        tname = target.id if isinstance(target, ast.Name) else None
        outer = enclosing.get(id(node))
        # candidate defs: same-name functions nested in the enclosing
        # builder first, falling back to anywhere in the module
        candidates = []
        if tname is not None and outer is not None:
            candidates = [n for n in ast.walk(outer)
                          if isinstance(n, (ast.FunctionDef,
                                            ast.AsyncFunctionDef))
                          and n.name == tname]
        if tname is not None and not candidates:
            candidates = [n for n in top_fns if n.name == tname]
        donate = ()
        for kw in node.keywords:
            if kw.arg == "donate_argnums":
                donate = _resolve_donate_expr(kw.value, outer) or ()
        out.append({"fn": tname, "lineno": node.lineno, "donated": donate,
                    "candidates": [[a.arg for a in c.args.args]
                                   for c in candidates]})
    return out


def _contract_violations(params, donated, required, fn_name, lineno):
    """Violations for ONE candidate parameter list (empty = clean)."""
    names = {params[i] for i in donated if i < len(params)}
    out = []
    present_required = required & set(params)
    missing = present_required - names
    if missing:
        out.append((
            lineno, f"{fn_name}:missing:{sorted(missing)}",
            f"jitted {fn_name}({', '.join(params)}) does not donate "
            f"carried state {sorted(missing)} — peak memory silently "
            "doubles for those buffers"))
    bad = names & FORBIDDEN
    if bad:
        out.append((
            lineno, f"{fn_name}:forbidden:{sorted(bad)}",
            f"jitted {fn_name} donates {sorted(bad)} — these are "
            "shared/read-again inputs, donating them frees buffers "
            "the host still uses"))
    return out


def check_contract(module: _ad.Module, required, path) -> List[Tuple]:
    """[(lineno, key, message)] contract violations for one module. A
    site with several same-named candidate defs passes if ANY candidate
    satisfies the contract."""
    out = []
    sites = [s for s in jit_donation_sites(module) if s["candidates"]]
    if not sites:
        return [(0, f"{path}:no-jit",
                 f"{path}: no jax.jit sites with resolvable functions "
                 "found — the donation contract has nothing to check "
                 "(update the pass if the builder moved)")]
    for s in sites:
        per_candidate = [
            _contract_violations(params, s["donated"], required,
                                 s["fn"], s["lineno"])
            for params in s["candidates"]
            if required & set(params)]
        if per_candidate and all(per_candidate):
            out.extend(per_candidate[0])
    return out


# ------------------------------------------------------------ jaxpr checks
def _flatten_positions(args):
    import jax

    spans = []
    start = 0
    for a in args:
        leaves = jax.tree.flatten(a)[0]
        spans.append((start, start + len(leaves)))
        start += len(leaves)
    return spans, start


def check_aliasable(closed_jaxpr, example_args, donated_positions,
                    label, require_output_alias=True) -> List[str]:
    """Each donated leaf must be consumed by the program; when the
    program returns its carry, each donated leaf's aval must also appear
    among the outputs (else XLA cannot alias and the donation is a
    silent no-op)."""
    jaxpr = closed_jaxpr.jaxpr
    spans, total = _flatten_positions(example_args)
    if total != len(jaxpr.invars):
        return [f"{label}: example args flatten to {total} leaves but "
                f"the jaxpr has {len(jaxpr.invars)} invars — the "
                "donation map is stale"]
    used = set()
    from .. import jaxpr_driver as _jd

    for eqn in _jd.iter_eqns(closed_jaxpr):
        for v in eqn.invars:
            used.add(id(v))
    out_avals = {}
    for v in jaxpr.outvars:
        a = getattr(v, "aval", None)
        if a is not None and hasattr(a, "shape"):
            k = (tuple(a.shape), str(a.dtype))
            out_avals[k] = out_avals.get(k, 0) + 1
    msgs = []
    for pos in donated_positions:
        lo, hi = spans[pos]
        for v in jaxpr.invars[lo:hi]:
            a = v.aval
            if id(v) not in used and v not in jaxpr.outvars:
                msgs.append(
                    f"{label}: donated leaf {a.shape}/{a.dtype} (arg "
                    f"{pos}) is never consumed by the program — dead "
                    "donation, likely a stale argnum")
                continue
            if require_output_alias:
                k = (tuple(a.shape), str(a.dtype))
                if out_avals.get(k, 0) > 0:
                    out_avals[k] -= 1
                else:
                    msgs.append(
                        f"{label}: donated leaf {a.shape}/{a.dtype} "
                        f"(arg {pos}) matches NO output aval — XLA "
                        "cannot alias it; the donation is a no-op and "
                        "the buffer is simply destroyed")
    return msgs


def run_jaxpr_checks(programs) -> List[str]:
    import inspect

    msgs = []
    step = programs.train_step
    try:
        params = list(inspect.signature(step._step_fn).parameters)
    except (TypeError, ValueError):
        params = []
    if params and set(params) & REQUIRED_STEP:
        donated = [i for i, p in enumerate(params) if p in REQUIRED_STEP]
    else:
        # jit wrapper hides the signature: fall back to the known step
        # layout (train_vals, frozen, opt, batch, label, key, lr, t,
        # rescale[, scaler_state])
        donated = [0, 2, 5, 7] + (
            [9] if len(step._last_avals) == 10 else [])
    msgs += check_aliasable(programs.train_jaxpr, step._last_avals,
                            donated, "TrainStep")
    _, decode_jaxpr, _, decode_args = programs.decode_programs()
    msgs += check_aliasable(decode_jaxpr, decode_args, [1],
                            "InferStep.decode",
                            require_output_alias=False)
    pj, dj, pargs, dargs = programs.paged_programs()
    msgs += check_aliasable(pj, pargs, [1], "InferStep.prefill_paged")
    msgs += check_aliasable(dj, dargs, [1], "InferStep.decode_iter")
    return msgs


# --------------------------------------------------- use-after-donate AST
def check_use_after_donate(module: _ad.Module,
                           donating=DONATING_CALLS) -> List[Tuple]:
    """[(lineno, key, message)]: donated args read after the donating
    call, or never rebound from its result."""
    out = []
    for cls in module.classes.values():
        for fn in cls.body:
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            out.extend(_check_fn(cls.name, fn, donating))
    return out


def _donating_call_in(stmt, donating):
    for node in ast.walk(stmt):
        if isinstance(node, ast.Call) and \
                isinstance(node.func, ast.Attribute) and \
                node.func.attr in donating:
            pos = donating[node.func.attr]
            if pos < len(node.args):
                key = _ad.dotted(node.args[pos])
                if key is not None:
                    return node, key
    return None, None


def _assign_targets(stmt):
    keys = set()
    targets = []
    if isinstance(stmt, ast.Assign):
        targets = stmt.targets
    elif isinstance(stmt, (ast.AugAssign, ast.AnnAssign)):
        targets = [stmt.target]
    for t in targets:
        stack = [t]
        while stack:
            n = stack.pop()
            if isinstance(n, (ast.Tuple, ast.List)):
                stack.extend(n.elts)
            elif isinstance(n, ast.Starred):
                stack.append(n.value)
            else:
                k = _ad.dotted(n)
                if k is not None:
                    keys.add(k)
    return keys


_COMPOUND = (ast.For, ast.AsyncFor, ast.While, ast.If, ast.With,
             ast.AsyncWith, ast.Try, ast.FunctionDef, ast.AsyncFunctionDef,
             ast.ClassDef)


def _check_fn(cls_name, fn, donating):
    out = []
    # only SIMPLE statements participate: compound containers are
    # containers — their nested simple statements are walked separately
    # (a For holding the donating call must not shadow the per-statement
    # rebind analysis of its body)
    stmts = sorted((s for s in _ad.walk_statements(fn.body)
                    if not isinstance(s, _COMPOUND)),
                   key=lambda s: s.lineno)
    pending = None  # (key, call_lineno, callee)
    for stmt in stmts:
        if pending is not None:
            key, call_ln, callee = pending
            reads = [n for n in ast.walk(stmt)
                     if isinstance(n.ctx if hasattr(n, "ctx") else None,
                                   ast.Load) and _ad.dotted(n) == key]
            rebinds = key in _assign_targets(stmt)
            if reads and not rebinds:
                out.append((
                    stmt.lineno,
                    f"{cls_name}.{fn.name}:{key}:use-after",
                    f"{cls_name}.{fn.name} reads {key} at line "
                    f"{stmt.lineno} AFTER donating it into "
                    f"{callee}(...) at line {call_ln} — the buffer is "
                    "deleted (or stale) once the dispatch consumes it"))
                pending = None
                continue
            if rebinds:
                pending = None
        node, key = _donating_call_in(stmt, donating)
        if node is not None:
            if key in _assign_targets(stmt):
                continue  # rebound in the same statement — the pattern
            pending = (key, node.lineno, node.func.attr)
    if pending is not None:
        key, call_ln, callee = pending
        out.append((
            call_ln, f"{cls_name}.{fn.name}:{key}:lost",
            f"{cls_name}.{fn.name} donates {key} into {callee}(...) at "
            f"line {call_ln} but never rebinds it from the result — the "
            "live carry is lost and the next dispatch reuses a deleted "
            "buffer"))
    return out


@register
class DonationPass(AnalysisPass):
    name = "donation"
    ir = "jaxpr"
    description = ("donate_argnums cover the carried state, donations "
                   "are consumed+aliasable, no host use-after-donate")

    def run(self, ctx):
        findings = []
        for path, required in ((STEP_PY, REQUIRED_STEP),
                               (INFER_PY, REQUIRED_INFER)):
            mod = ctx.ast.module(path)
            for ln, key, msg in check_contract(mod, required, path):
                findings.append(self.finding("contract", path, ln,
                                             key=key, message=msg))
        for ln, key, msg in check_use_after_donate(
                ctx.ast.module(BATCHER_PY)):
            findings.append(self.finding("use-after-donate", BATCHER_PY,
                                         ln, key=key, message=msg))
        for msg in run_jaxpr_checks(ctx.programs):
            findings.append(self.finding(
                "aliasable", STEP_PY, 0, key=msg[:80], message=msg))
        return findings
